"""On-device batched token sampling: greedy / temperature / top-k / top-p,
frequency/presence/repetition penalties, per-sequence RNG streams, and
logprobs.

Fully vectorized over the batch with per-sequence parameters so one jitted
sample call serves a mixed batch (greedy and sampled requests together).
Role-equivalent of the sampling-parameter surface the reference validates in
lib/llm/src/protocols/openai/validate.rs:95-125 and forwards to its engines
— here the sampler IS the engine's, so the parameters are implemented, not
just forwarded.

TPU notes: everything is [B, V]-vectorized (no per-sequence Python), the
penalty histogram is built with one scatter-add per step, and per-sequence
RNG uses raw threefry key data ([B, 2] uint32 rows: (stream_id, counter)) so
hosts can construct keys with numpy — no device dispatch per key.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def penalty_count_tables(
    hist: jax.Array,  # [B, L] int32 token history (prompt + generated)
    hist_len: jax.Array,  # [B] int32 total valid tokens in hist
    prompt_len: jax.Array,  # [B] int32 prompt prefix length within hist
    vocab_size: int,
) -> tuple[jax.Array, jax.Array]:
    """Scatter the history into per-vocab tables: (out_counts [B, V] —
    generated-token counts, seen [B, V] — prompt+generated occupancy).

    These tables are the penalty state. The horizon program builds them
    ONCE per dispatch and updates them with each on-device sampled token
    (history is append-only during a horizon), instead of paying the
    [B, L] upload + scatter every step."""
    B = hist.shape[0]
    L = hist.shape[1]
    V = vocab_size
    idx = jnp.arange(L)[None, :]
    valid = idx < hist_len[:, None]  # [B, L]
    is_out = valid & (idx >= prompt_len[:, None])
    rows = jnp.arange(B)[:, None]
    safe_hist = jnp.clip(hist, 0, V - 1)
    out_counts = jnp.zeros((B, V), jnp.float32).at[rows, safe_hist].add(
        is_out.astype(jnp.float32)
    )
    seen = jnp.zeros((B, V), jnp.float32).at[rows, safe_hist].max(
        valid.astype(jnp.float32)
    )
    return out_counts, seen


def apply_penalties_from_tables(
    logits: jax.Array,  # [B, V] f32
    out_counts: jax.Array,  # [B, V] f32 generated-token counts
    seen: jax.Array,  # [B, V] f32 (>0 where token appeared at all)
    frequency_penalty: jax.Array,  # [B] f32; 0 disables
    presence_penalty: jax.Array,  # [B] f32; 0 disables
    repetition_penalty: jax.Array,  # [B] f32; 1 disables
) -> jax.Array:
    """vLLM-semantics penalties from precomputed count tables:

    * frequency/presence apply over GENERATED tokens only:
      ``logits -= freq * count(v) + pres * [count(v) > 0]``
    * repetition (HF-style) applies over prompt+generated seen tokens:
      positive logits divided by rp, negative multiplied by rp.

    A lane with freq=0, pres=0, rep=1 passes through bit-exactly, so one
    program serves mixed penalty/plain batches."""
    logits = (
        logits
        - frequency_penalty[:, None] * out_counts
        - presence_penalty[:, None] * (out_counts > 0)
    )
    rp = repetition_penalty[:, None]
    penalized = jnp.where(logits > 0, logits / rp, logits * rp)
    return jnp.where(seen > 0, penalized, logits)


def apply_penalties(
    logits: jax.Array,  # [B, V] f32
    hist: jax.Array,  # [B, L] int32 token history (prompt + generated)
    hist_len: jax.Array,  # [B] int32 total valid tokens in hist
    prompt_len: jax.Array,  # [B] int32 prompt prefix length within hist
    frequency_penalty: jax.Array,  # [B] f32; 0 disables
    presence_penalty: jax.Array,  # [B] f32; 0 disables
    repetition_penalty: jax.Array,  # [B] f32; 1 disables
) -> jax.Array:
    """Single-step penalties: build the tables and apply (see the table
    variants above for the horizon program's amortized form)."""
    out_counts, seen = penalty_count_tables(
        hist, hist_len, prompt_len, logits.shape[-1]
    )
    return apply_penalties_from_tables(
        logits, out_counts, seen,
        frequency_penalty, presence_penalty, repetition_penalty,
    )


def apply_repetition_penalty_from_prompt(
    logits: jax.Array,  # [V] or [B, V]
    prompt: jax.Array,  # [T] int32 (padded; positions >= valid_len ignored)
    valid_len: jax.Array,  # scalar int32
    repetition_penalty: jax.Array,  # scalar f32; 1 disables
) -> jax.Array:
    """Prompt-only repetition penalty for the prefill-sampled first token
    (frequency/presence are zero by definition at the first token)."""
    squeeze = logits.ndim == 1
    if squeeze:
        logits = logits[None, :]
    V = logits.shape[-1]
    valid = jnp.arange(prompt.shape[0]) < valid_len
    seen = jnp.zeros((V,), jnp.float32).at[jnp.clip(prompt, 0, V - 1)].max(
        valid.astype(jnp.float32)
    )
    rp = repetition_penalty
    penalized = jnp.where(logits > 0, logits / rp, logits * rp)
    out = jnp.where(seen[None, :] > 0, penalized, logits)
    return out[0] if squeeze else out


def apply_repetition_penalty_packed(
    logits: jax.Array,  # [N, V] per-segment last-token logits
    tokens: jax.Array,  # [P] int32 packed prompt tokens
    segment_ids: jax.Array,  # [P] int32; -1 marks padding
    repetition_penalty: jax.Array,  # [N] f32; 1 disables
) -> jax.Array:
    """Per-segment prompt repetition penalty for the packed-prefill first
    token: each segment's seen-set is scattered from its own tokens."""
    N, V = logits.shape
    valid = (segment_ids >= 0).astype(jnp.float32)
    rows = jnp.clip(segment_ids, 0, N - 1)
    seen = jnp.zeros((N, V), jnp.float32).at[rows, jnp.clip(tokens, 0, V - 1)].max(
        valid
    )
    rp = repetition_penalty[:, None]
    penalized = jnp.where(logits > 0, logits / rp, logits * rp)
    return jnp.where(seen > 0, penalized, logits)


def spec_accept_len(
    sampled: jax.Array,  # [B, S] i32 — model tokens at draft positions
    drafts: jax.Array,  # [B, S-1] i32 — draft tokens fed at steps 1..S-1
    draft_len: jax.Array,  # [B] i32 — valid drafts per lane
) -> jax.Array:
    """Vectorized draft acceptance: number of accepted draft tokens per
    lane. Draft d_{h+1} (fed at step h+1) is accepted iff it equals the
    model's token t_h at the previous position AND every earlier draft
    matched too — the longest-matching-prefix rule of draft-k/verify-1
    speculative decoding. Works identically under greedy and temperature
    sampling because `sampled` is already the model's (argmax or keyed
    categorical) choice per position — acceptance is pure id comparison.
    """
    S = sampled.shape[1]
    step = jnp.arange(1, S)[None, :]  # draft index 1..S-1
    match = (sampled[:, :-1] == drafts) & (step <= draft_len[:, None])
    return jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)


MAX_EOS_IDS = 4  # eos-id slots carried into the jitted programs


def mask_eos_logits(
    logits: jax.Array,  # [B, V] or [V]
    eos_ids: jax.Array,  # [B, K] or [K] int32; -1 pads unused slots
    suppress: jax.Array,  # [B] or scalar bool — min_tokens not reached
) -> jax.Array:
    """min_tokens support, done the vLLM way: while a sequence has not
    generated its minimum, its EOS logits are masked to -inf so EOS cannot
    be sampled at all (appending a suppressed EOS to the stream would still
    stop the HTTP-layer decoder — the mask keeps every layer consistent)."""
    squeeze = logits.ndim == 1
    if squeeze:
        logits = logits[None]
        eos_ids = eos_ids[None]
        suppress = jnp.asarray(suppress).reshape(1)
    B, V = logits.shape
    rows = jnp.arange(B)[:, None]
    valid = eos_ids >= 0
    is_eos = jnp.zeros((B, V), bool).at[
        rows, jnp.clip(eos_ids, 0, V - 1)
    ].max(valid)
    out = jnp.where(is_eos & suppress[:, None], NEG_INF, logits)
    return out[0] if squeeze else out


# Candidate-pool width for top-k/top-p filtering. Two full [B, V] sorts
# per step (tens of ms at 128k vocab) are replaced by one lax.top_k(C)
# pass over a descending candidate pool, and that pass (6 to 7 ms a step at
# 152k vocab on a v5e) runs only in a step that holds a sampled row with a
# restriction: `sample_tokens` puts the pool under one lax.cond. Rows with
# NO restriction (top_k<=0 and top_p>=1) bypass the pool entirely, in the
# result and in the cost — they draw a full categorical over the
# temperature-scaled vocab, so the default sampling distribution stays
# exact at any temperature. Restricted rows are exact whenever their
# support fits the pool (always true for vocab <= C and any top_k <= C; a
# nucleus is truncated to the pool only if its mass extends past the top
# 256 temperature-scaled candidates — ~1e-4 mass on real models near
# temp 1); top_k > C clamps to C.
SAMPLE_CANDIDATES = 256


def draw_restrictions(temperature, top_p, top_k):
    """(unrestricted [B] bool, need_pool scalar bool) of one batch's
    sampling parameters: which rows draw over the full vocab, and whether
    any sampled row (temperature > 0) draws from the candidate pool. Plain
    array arithmetic, so the device (jax arrays, inside `sample_tokens`)
    and the host (the engine's numpy lane arrays, for its counter) decide
    by the same rule."""
    wide_nucleus = (top_k <= 0) & (top_p >= 0.99) & (temperature > 1.25)
    unrestricted = ((top_k <= 0) & (top_p >= 1.0)) | wide_nucleus
    return unrestricted, ((temperature > 0.0) & ~unrestricted).any()


def _filtered_candidates(
    scaled: jax.Array,  # [B, V] temperature-scaled logits
    top_p: jax.Array,
    top_k: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Mask the candidate pool to the top-k / nucleus support.

    Returns (vals [B, C] descending filtered logits, idx [B, C] vocab ids):
    a compact candidate representation — sample over C, map back via idx.
    """
    B, V = scaled.shape
    C = min(SAMPLE_CANDIDATES, V)
    vals, idx = jax.lax.top_k(scaled, C)  # [B, C] descending

    # top-k: candidates are sorted, so the mask is positional
    k = jnp.clip(jnp.where(top_k <= 0, C, jnp.minimum(top_k, C)), 1, C)
    pos = jnp.arange(C)[None, :]
    vals = jnp.where(pos >= k[:, None], NEG_INF, vals)

    # top-p (nucleus): keep the smallest prefix of the candidate
    # distribution with cumulative probability >= top_p
    probs = jax.nn.softmax(vals, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < top_p[:, None]
    vals = jnp.where(keep, vals, NEG_INF)
    return vals, idx


def sample_tokens(
    logits: jax.Array,  # [B, V] float32
    rng: jax.Array,
    temperature: jax.Array,  # [B] f32; <=0 means greedy
    top_p: jax.Array,  # [B] f32 in (0, 1]; 1.0 disables
    top_k: jax.Array,  # [B] int32; 0 disables
    keys: Optional[jax.Array] = None,  # [B, 2] uint32 raw threefry key data
) -> jax.Array:
    """Returns sampled token ids [B] int32.

    `rng` seeds the whole batch; when `keys` is given, each row samples from
    its own threefry stream (per-request `seed` support) and `rng` is
    ignored for the draw.

    Unrestricted rows (top_k<=0, top_p>=1) draw over the full vocab —
    exact at any temperature. Restricted rows draw from the top
    SAMPLE_CANDIDATES pool: exact for top_k <= pool, and a nucleus
    truncates to the pool with ~1e-4 lost mass near temperature 1. At
    high temperature the tail past the pool is materially heavier, so
    rows with an effectively-unrestricting nucleus (top_p >= 0.99,
    no top_k) and temperature > 1.25 are routed to the full-vocab draw
    instead — trading the top 1% tail cut (which high temperature makes
    ill-defined anyway) for no pool truncation.

    What is computed when: the argmax and the full-vocab draw always, for
    every row (the draw's own argmax over the noised logits is the larger of
    the two, and every sampled row needs it). The pool (top_k over the
    vocab, its masks, softmax, cumulative sum, its own draw and the map
    back to vocab ids) only when
    the batch holds a sampled row (temperature > 0) that restricts its
    draw — one lax.cond on a predicate reduced from the three parameter
    arrays, so a greedy row's top_k or an idle lane costs the batch no
    pool. Every row's token is the same either way: a row's draw reads
    its own key and its own parameters only.
    """
    greedy_ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / temp
    unrestricted, need_pool = draw_restrictions(temperature, top_p, top_k)
    if keys is not None:
        def draw(lg):  # [B, N] -> [B]: row i from its own key
            def row(kd, row_lg):
                k = jax.random.wrap_key_data(kd.astype(jnp.uint32))
                return jax.random.categorical(k, row_lg)

            return jax.vmap(row)(keys, lg).astype(jnp.int32)
    else:
        def draw(lg):
            return jax.random.categorical(rng, lg, axis=-1).astype(jnp.int32)

    full_choice = draw(scaled)

    def through_pool():
        vals, idx = _filtered_candidates(scaled, top_p, top_k)
        pool_sampled = jnp.take_along_axis(idx, draw(vals)[:, None], axis=-1)[:, 0]
        return jnp.where(unrestricted, full_choice, pool_sampled)

    sampled = jax.lax.cond(need_pool, through_pool, lambda: full_choice)
    return jnp.where(temperature <= 0.0, greedy_ids, sampled)


def surface_wanted(want_logprobs):
    """Scalar bool: whether any lane of one batch asked for log-probs, and
    so whether `sample_tokens_full` computes the surface in this step. Plain
    array arithmetic, as `draw_restrictions`: the device asks it of the
    program's input and the host of the lane array it is about to send."""
    return want_logprobs.any()


def sample_tokens_full(
    logits: jax.Array,  # [B, V] float32
    rng: jax.Array,
    temperature: jax.Array,
    top_p: jax.Array,
    top_k: jax.Array,
    want_logprobs: jax.Array,  # [B] bool; a lane whose request set `logprobs`
    keys: Optional[jax.Array] = None,
    num_top: int = 20,  # the OpenAI top_logprobs ceiling
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """sample_tokens + logprob surface.

    Returns (tokens [B] i32, chosen_logprob [B] f32,
    top_ids [B, num_top] i32, top_logprobs [B, num_top] f32). Logprobs are
    of the model's raw distribution (pre temperature/top-k/top-p), matching
    the OpenAI `logprobs` contract.

    The surface (`log_softmax` over the vocabulary, the chosen id's value
    and the top `num_top`: 0.5 ms a step at 130,000 ids on a v5e) is
    computed only in a step where some lane asked: one lax.cond on
    `surface_wanted`, as the candidate pool's. Where one asks, every lane's
    surface is computed (the host drops it for the others); where none does,
    all three come back zeros of the same shapes. The tokens are the same
    either way.
    """
    tokens = sample_tokens(logits, rng, temperature, top_p, top_k, keys=keys)
    B = logits.shape[0]

    def surface():
        logz = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        chosen = jnp.take_along_axis(logz, tokens[:, None], axis=-1)[:, 0]
        top_lps, top_ids = jax.lax.top_k(logz, num_top)
        return chosen, top_ids.astype(jnp.int32), top_lps

    def nothing():
        return (
            jnp.zeros((B,), jnp.float32),
            jnp.zeros((B, num_top), jnp.int32),
            jnp.zeros((B, num_top), jnp.float32),
        )

    chosen, top_ids, top_lps = jax.lax.cond(
        surface_wanted(want_logprobs), surface, nothing
    )
    return tokens, chosen, top_ids, top_lps


def make_key_data(stream_id: int, counter: int):
    """Host-side raw threefry key row for sample_tokens(keys=...): a
    (stream, counter) pair IS a valid independent threefry stream — no
    device work to build one. numpy only (callable from the engine's host
    loop and from follower replay)."""
    import numpy as np

    return np.array(
        [np.uint32(stream_id & 0xFFFFFFFF), np.uint32(counter & 0xFFFFFFFF)],
        np.uint32,
    )
