"""The four step programs, written once for the families that are their
layers: `prefill_packed`, `prefill`, `prefill_chunk` and `decode` of the
latent (`mla_moe`), hybrid state-space (`hybrid_ssm`), short-convolution
(`conv_moe`) and Mamba-2 (`ssm2_moe`) families, with the head, the refused
entry points and the draw that a jit leaves as it is.

A family module holds its config, the draw of its weights and its layer
bodies (`models.layer_body` functions: one mixer, one feed-forward), and one
`Family`: which body a layer takes in each program and which of the
program's values that body is given. A program here computes what all of its
layers share, embeds, walks the layers and takes the logits; it knows no
family. What a layer keeps rides in `k_cache[i]` and `v_cache[i]`: pages of
keys and values, a slot's two arrays (one row a lane and one more, the null
lane's, that padding writes to), a latent plane or a slot's one array with
nothing beside it, or nothing at all (`models.CacheKind`).

The grouped-query family (`models/llama.py`) keeps programs of its own: they
carry a mesh, the fused step, multimodal and context-parallel prefill and the
verify window (ROADMAP D13b).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Hashable, Mapping, Optional

import jax
import jax.numpy as jnp
from jax import lax

from dynamo_tpu.models import layer_cache_kinds
from dynamo_tpu.ops.attention import live_decode_lanes
from dynamo_tpu.ops.basics import rms_norm
from dynamo_tpu.ops.linear import linear

F32 = jnp.float32


@dataclass(frozen=True)
class Body:
    """What one kind of layer runs in one program. `fn` is its `layer_body`
    function, called `fn(x, layer, *kept, *taken, cfg=cfg, **static)`:
    `keeps` says how many arrays of its own the layer hands it and takes back
    (2: pages of keys and values, or a slot's two arrays; 1: a latent plane
    or a slot's one array; 0: a layer that keeps nothing), `takes` names the
    program's values it is given behind them, in its order, `static` those it
    is given by keyword beside `cfg`. It returns x, what the layer keeps and,
    where its feed-forward may route, what its experts counted behind that
    (`ops.moe.STEP_STATS`; None from a layer that routes nothing)."""

    fn: Callable
    keeps: int
    takes: tuple
    static: tuple = ()


@dataclass(frozen=True)
class Family:
    """A family's layers by program. `kind(cfg, i)` is the key of layer
    `i`'s row in each table. `whole` is the program for one whole prompt
    where a family gives it bodies of its own; without it a whole prompt is
    the packed program with one segment.

    What a program's layers share beyond what every family's do is the
    family's to compute, by the operations its programs have always used:
    `prepare[program](cfg, **values)` is called in front of the embedding
    with the program's values by name and returns more of them (the packed
    program's lane slots, rope's frequencies); `behind_embedding[name](cfg)`
    is called behind it. Two places, because the families' programs
    compiled so before they were written once; one is a later change's,
    judged by the programs' texts (`tests/test_mla_moe.py` PARENT_PROGRAMS).
    `embed_scale(cfg)`: what a token's embedding is multiplied by before the
    first layer, where the family's model scales it.

    A family whose paged layers are of two groups (`models.page_groups`:
    window and full attention) is handed both groups' tables side by side in
    the one table argument (`[..., 2 * nb]`: the full group's `nb` entries,
    then the window group's) and both groups' packed slots in the one slot
    argument (`[2 * P]`); what the programs here derive from a table reads
    the first group's half, and the family's `prepare` takes the halves
    apart."""

    kind: Callable[[Any, int], Hashable]
    packed: Mapping[Hashable, Body]
    chunk: Mapping[Hashable, Body]
    decode: Mapping[Hashable, Body]
    whole: Optional[Mapping[Hashable, Body]] = None
    prepare: Mapping[str, Callable] = field(default_factory=dict)
    behind_embedding: Mapping[str, Callable] = field(default_factory=dict)
    embed_scale: Optional[Callable[[Any], float]] = None


def _logits(x, params, cfg):
    h = rms_norm(x, params["final_norm"], cfg.rms_eps)
    w = params.get("lm_head")
    if w is None:
        return jnp.matmul(h, params["embed"].T.astype(h.dtype)).astype(F32)
    return linear(h, w).astype(F32)


def _first(cfg, paged: bool) -> int:
    """The first layer that keeps pages, or the first that keeps a slot a
    lane, by what the config declares (`layer_cache_kinds`)."""
    return next(
        i for i, kind in enumerate(layer_cache_kinds(cfg))
        if (kind.planes > 0 if paged else bool(kind.slot))
    )


def _page_size(cfg, k_cache) -> int:
    return k_cache[_first(cfg, True)].shape[2]


def _walk(family, program, params, cfg, tokens, k_cache, v_cache, values, stats):
    """The family's own values of this program, the embedding, then the
    layers in order, each with what it keeps. The expert layers' counters are
    appended to `stats` where a list is given. Returns x and the two
    containers; a family whose layers keep one plane has no second."""
    prepare = family.prepare.get(program)
    if prepare is not None:
        values.update(prepare(cfg, **values))
    x = params["embed"][tokens]
    if family.embed_scale is not None:
        x = x * jnp.asarray(family.embed_scale(cfg), x.dtype)
    for name, derive in family.behind_embedding.items():
        values[name] = derive(cfg)
    bodies = getattr(family, program)
    k_out, v_out = [], []
    for i, layer in enumerate(params["layers"]):
        body = bodies[family.kind(cfg, i)]
        kept = (k_cache[i], v_cache[i] if v_cache else None)[: body.keeps]
        x, *out = body.fn(
            x, layer, *kept, *(values[name] for name in body.takes),
            cfg=cfg, **{name: values[name] for name in body.static},
        )
        first, second = (*out[: body.keeps], None, None)[:2]
        k_out.append(first)
        v_out.append(second)
        counted = out[body.keeps:]
        if stats is not None and counted and counted[0] is not None:
            stats.append(counted[0])
    return x, tuple(k_out), tuple(v_out) if v_cache else ()


def prefill_packed(
    family: Family,
    params: dict,
    cfg,
    tokens: jax.Array,  # [P] int32: several prompts packed back to back
    positions: jax.Array,  # [P] int32: restart at 0 per segment
    segment_ids: jax.Array,  # [P] int32; -1 marks padding
    slot_indices: jax.Array,  # [P] int32 flat cache slots per token
    k_cache: tuple,  # per layer: what the layer keeps first
    v_cache: tuple,  # per layer: what it keeps second; () for a latent cache
    last_idx: jax.Array,  # [N] int32
    *,
    state_slots: Optional[jax.Array] = None,  # [N] int32: the lane slot of each segment
    mesh=None,
    stats: Optional[list] = None,
) -> tuple[jax.Array, tuple, tuple]:
    """Fresh prompts, nothing earlier in the cache: a segment's recurrent
    state starts from zero at its position 0 and ends in its slot; a segment
    that holds no prompt sends what is computed for it to the null lane (the
    family's `prepare["packed"]`). Returns (logits [N, V], caches)."""
    values = dict(
        positions=positions, segment_ids=segment_ids, slot_indices=slot_indices,
        last_idx=last_idx, valid=segment_ids >= 0, mesh=mesh,
        page_size=_page_size(cfg, k_cache),
    )
    if state_slots is not None:
        null = k_cache[_first(cfg, False)].shape[0] - 1
        values.update(state_slots=state_slots, null=null)
    x, k_out, v_out = _walk(
        family, "packed", params, cfg, tokens, k_cache, v_cache, values, stats
    )
    return _logits(x[last_idx], params, cfg), k_out, v_out


def prefill(
    family: Family, params, cfg, tokens, valid_len, k_cache, v_cache, block_table,
    *, state_slots=None, mesh=None, attn_head_axis=None,
):
    """One whole prompt (padded to a bucket): the packed program with one
    segment, or the family's `whole` bodies over the same values.
    `state_slots`: its lane slot (scalar). Returns (logits [V], caches)."""
    P = tokens.shape[0]
    bs = _page_size(cfg, k_cache)
    pos = jnp.arange(P, dtype=jnp.int32)
    live = pos < valid_len
    slots = jnp.where(live, block_table[pos // bs] * bs + pos % bs, 0)
    # the sequence's lane slot as the one segment's, where the family keeps
    # one; made where each branch's program has always made it
    seg_slots = lambda: None if state_slots is None else jnp.reshape(state_slots, (1,))
    if family.whole is None:
        logits, k_out, v_out = prefill_packed(
            family, params, cfg, tokens, pos, jnp.where(live, 0, -1), slots,
            k_cache, v_cache, (valid_len - 1)[None], state_slots=seg_slots(),
        )
        return logits[0], k_out, v_out
    last_idx = (valid_len - 1)[None]
    values = dict(
        positions=pos, valid=live, valid_len=valid_len, slot_indices=slots,
        last_idx=last_idx, seg_slots=seg_slots(), mesh=mesh,
        head_axis=attn_head_axis, block_table=block_table, page_size=bs,
    )
    x, k_out, v_out = _walk(
        family, "whole", params, cfg, tokens, k_cache, v_cache, values, None
    )
    return _logits(x[last_idx], params, cfg)[0], k_out, v_out


def prefill_chunk(
    family: Family,
    params: dict,
    cfg,
    tokens: jax.Array,  # [C] int32
    chunk_start: jax.Array,  # scalar int32
    valid_len: jax.Array,  # scalar int32: total prompt length
    k_cache: tuple,
    v_cache: tuple,
    block_table: jax.Array,  # [max_nb] int32
    *,
    state_slots: Optional[jax.Array] = None,  # scalar int32: the sequence's lane slot
    mesh=None,
    stats: Optional[list] = None,
) -> tuple[jax.Array, tuple, tuple]:
    """One chunk of a chunked prefill: a recurrent state is taken from the
    sequence's slot (zero at `chunk_start` 0) and left there; the chunk's
    rows are written, then it attends over what the cache holds of its
    prompt."""
    C = tokens.shape[0]
    bs = _page_size(cfg, k_cache)
    positions = chunk_start + jnp.arange(C, dtype=jnp.int32)
    valid = positions < valid_len
    # the table is read behind its end by a last chunk's padded tail:
    # those rows go to the null block
    n = block_table.shape[0]
    page = jnp.where(positions // bs < n, block_table[jnp.minimum(positions // bs, n - 1)], 0)
    slots = jnp.where(valid, page * bs + positions % bs, 0)
    values = dict(
        positions=positions, valid=valid, slot_indices=slots,
        block_table=block_table, chunk_start=chunk_start, mesh=mesh,
        page_size=bs,
    )
    if state_slots is not None:
        values["lane_slot"] = jnp.reshape(state_slots, ())
    x, k_out, v_out = _walk(
        family, "chunk", params, cfg, tokens, k_cache, v_cache, values, stats
    )
    idx = jnp.clip(valid_len - 1 - chunk_start, 0, C - 1)
    return _logits(x[idx][None, :], params, cfg)[0], k_out, v_out


def decode(
    family: Family,
    params: dict,
    cfg,
    tokens: jax.Array,  # [B] int32
    positions: jax.Array,  # [B] int32
    k_cache: tuple,
    v_cache: tuple,
    block_tables: jax.Array,  # [B, max_blocks] int32
    slot_indices: jax.Array,  # [B] int32; a slot in the null block = idle lane
    *,
    mesh=None,
    attn_head_axis=None,
    stats: Optional[list] = None,
    settle: bool = True,
) -> tuple[jax.Array, tuple, tuple]:
    """One decode step for a batch; lane b's recurrent state is row b of the
    slot arrays. A lane whose row goes to the null block holds no decoding
    sequence: it reads no page, is given to no expert, and its slot stays as
    it is. `settle`: this step is the last of its dispatch, or the only one;
    a dispatch of several steps says false for the others, and a layer whose
    body lists it may then leave what it keeps unwritten and hand its next
    step, in the array's place, what that step needs to write it
    (`ops.pallas_ssm.Deferred`). Returns (logits [B, V], caches)."""
    live = live_decode_lanes(k_cache[_first(cfg, True)], slot_indices)
    values = dict(
        positions=positions, live=live, context=jnp.where(live, positions + 1, 0),
        block_tables=block_tables, slot_indices=slot_indices, mesh=mesh,
        head_axis=attn_head_axis, page_size=_page_size(cfg, k_cache),
        settle=settle,
    )
    x, k_out, v_out = _walk(
        family, "decode", params, cfg, tokens, k_cache, v_cache, values, stats
    )
    return _logits(x, params, cfg), k_out, v_out


def bound(family: Family) -> tuple:
    """(prefill_packed, prefill, prefill_chunk, decode) of `family`: what its
    module exposes under those names for the runner (`models.forward_for`)."""
    return tuple(
        functools.partial(program, family)
        for program in (prefill_packed, prefill, prefill_chunk, decode)
    )


def _not_served(what: str, family: str):
    def refuse(*_a, **_k):
        raise NotImplementedError(f"{what} is not implemented for {family}")

    return refuse


def refused(family: str, verify: str = "speculative verification") -> tuple:
    """(prefill_mm, prefill_context_parallel, embed_pooled, decode_verify):
    the grouped-query family's other entry points, refused in words that
    name `family`; `verify` says why a draft cannot be verified."""
    return tuple(_not_served(what, family) for what in (
        "multimodal prefill", "context-parallel prefill", "pooled embedding",
        verify,
    ))


# ------------------------------------------------- the draw under a jit


def normal(key, shape):
    """A float32 normal draw that a jit leaves as it is: behind the barrier
    the compiler cannot fold the draw's own constants into what multiplies
    or divides it next, which rounds one value in some thousands differently
    from the same draw made outside a jit (the reference's)."""
    return lax.optimization_barrier(jax.random.normal(key, shape, dtype=F32))


def dense(key, shape, fan_in, dtype):
    # the divisor behind a barrier too: a division by a known constant is
    # compiled as a product with its reciprocal
    by = lax.optimization_barrier(jnp.sqrt(F32(fan_in)))
    return (normal(key, shape) / by).astype(dtype)
