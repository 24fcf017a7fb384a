"""The hybrid state-space family (`models/hybrid_ssm.py`) at a toy size of the
same pattern (a toy period of 3 with the attention layer in its middle: 3
Mamba layers and 2 attention layers in 5), held to the plain float32
reference of `cellbench/reference/hybrid_ssm.py` on logits; the state slots
through the runner and the engine; what the factory refuses for it; and the
rule that finds a family by its `model_type`.

Tolerances. In float32 the program and the reference compute the same
numbers in another order (a chunk's scan from a carried state against one
loop from zero; paged attention against per-head attention over the whole
sequence; fused projections): 2e-5 of the logits' spread is ten times what
such runs read (1e-6 to 2e-6) and a thousandth of the smallest difference a
wrong form makes (a state not reset at a pack's boundary, a tail from the
neighbouring sequence, a missing `D` or bias read 1e-2 and more). In
bfloat16 the toy reads 0.01 to 0.02, the width of bfloat16's mantissa
through five layers; 0.05 holds it to the same order. Where two of the
program's own forms are compared the arithmetic is the same: a reused slot
against a fresh one runs one program twice and must be equal to the last
bit; a horizon against single steps is two programs, which must give the
same tokens and log-probs and states to float32 roundings.
"""

from __future__ import annotations

import asyncio
import dataclasses
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from cellbench.compare import logit_error  # noqa: E402
from cellbench.reference import hybrid_ssm as R  # noqa: E402
from dynamo_tpu.engine.jax_engine.model_runner import ModelRunner  # noqa: E402
from dynamo_tpu.models import (  # noqa: E402
    config_from_model_dir, layer_cache_kinds, recurrent_layers,
)
from dynamo_tpu.models import hybrid_ssm as M  # noqa: E402
from dynamo_tpu.models import llama as L  # noqa: E402
from dynamo_tpu.models import mla_moe  # noqa: E402
from dynamo_tpu.ops.sampling import MAX_EOS_IDS  # noqa: E402

HF = {
    "model_type": "jamba", "hidden_size": 64, "intermediate_size": 160,
    "num_hidden_layers": 5, "num_attention_heads": 4, "num_key_value_heads": 1,
    "attn_layer_period": 3, "attn_layer_offset": 1, "expert_layer_period": 2,
    "expert_layer_offset": 1, "num_experts": 1, "num_experts_per_tok": 1,
    "mamba_d_state": 8, "mamba_d_conv": 4, "mamba_dt_rank": 8,
    "mamba_expand": 2, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "hidden_act": "silu", "vocab_size": 300, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": True, "sliding_window": None,
    "max_position_embeddings": 128, "use_mamba_kernels": True,
    "num_logits_to_keep": 1,
}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
BS, NB, MAX_BLOCKS, LANES = 4, 48, 8, 3
F32_TOL, BF16_TOL = 2e-5, 0.05


@functools.lru_cache(maxsize=None)
def reference_weights():
    d = R.dims(HF)
    *layers, top = list(R.seeded_layers(d, 0))
    return d, layers, top


def toy(attn_impl: str = "xla", dtype=jnp.float32):
    """(config, params handed over from the reference's own draw, the
    reference's dims, layers and top)."""
    cfg = dataclasses.replace(M.HybridSsmConfig.from_hf_dict(HF), attn_impl=attn_impl)
    d, layers, top = reference_weights()
    keep32 = ("A_log", "D", "b_dt")
    params = {
        "layers": [
            {k: v.astype(jnp.float32 if k in keep32 else dtype) for k, v in l.items()}
            for l in layers
        ],
        "embed": top["embed"].astype(dtype),
        "final_norm": top["final_norm"].astype(dtype),
    }
    return cfg, params, d, layers, top


def caches(cfg, dtype=jnp.float32, fill: float = 0.0):
    """The runner's two containers for LANES lanes and the null lane: pages
    for an attention layer, the slot arrays for a Mamba layer, those filled
    with `fill` (a slot's content before a sequence starts must not count)."""
    state, tail = (s for s, _ in cfg.state_kind().slot)
    k = tuple(
        jnp.zeros((1, NB, BS, cfg.head_dim), dtype) if cfg.is_attn_layer(i)
        else jnp.full((LANES + 1,) + state, fill, jnp.float32)
        for i in range(cfg.num_layers)
    )
    v = tuple(
        jnp.zeros((1, NB, BS, cfg.head_dim), dtype) if cfg.is_attn_layer(i)
        else jnp.full((LANES + 1,) + tail, fill, jnp.float32)
        for i in range(cfg.num_layers)
    )
    return k, v


def prompt_tokens(n: int, seed: int) -> list[int]:
    return np.random.default_rng(seed).integers(3, HF["vocab_size"], n).tolist()


def pack(prompts: list[list[int]], tables: np.ndarray, P: int):
    tokens = np.zeros(P, np.int32)
    positions = np.zeros(P, np.int32)
    segments = np.full(P, -1, np.int32)
    slots = np.zeros(P, np.int32)
    last, at = [], 0
    for seg, p in enumerate(prompts):
        n = len(p)
        pos = np.arange(n, dtype=np.int32)
        tokens[at:at + n] = p
        positions[at:at + n] = pos
        segments[at:at + n] = seg
        slots[at:at + n] = tables[seg, pos // BS] * BS + pos % BS
        at += n
        last.append(at - 1)
    last += [0] * (LANES - len(last))
    return tuple(jnp.asarray(a) for a in (tokens, positions, segments, slots)), jnp.asarray(last, jnp.int32)


def greedy(B):
    """(keys, temps, top_ps, top_ks, want_lps): every lane greedy, every
    lane asking for its log-probs."""
    return (
        jnp.zeros((B, 2), jnp.uint32), jnp.zeros(B, jnp.float32),
        jnp.ones(B, jnp.float32), jnp.zeros(B, jnp.int32), jnp.ones(B, bool),
    )


def rel(got, want) -> float:
    """Root mean square of the centred differences over the reference's
    spread: `cellbench/compare.py`'s number over every id."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    centred = (got - got.mean(-1, keepdims=True)) - (want - want.mean(-1, keepdims=True))
    return float(np.sqrt(np.mean(centred ** 2)) / np.std(want))


def against_reference(sequences, rows, top_ids, top_lps, lower=None):
    d, layers, top = reference_weights()
    want = np.asarray(R.forward(layers, top, d, sequences, rows))
    served, reference, stds = [], [], []
    for i in range(len(sequences)):
        for r in range(len(rows)):
            ids = np.asarray(top_ids[i][r], np.int64)
            served.append([float(x) for x in top_lps[i][r]])
            reference.append([float(x) for x in want[i, r, ids]])
            stds.append(float(np.std(want[i, r])))
    return logit_error(served, reference, stds)["rms_rel"]


def tables_for(n_lanes: int = LANES) -> np.ndarray:
    t = np.zeros((n_lanes, MAX_BLOCKS), np.int32)
    for i in range(n_lanes):
        t[i] = np.arange(1 + i * MAX_BLOCKS, 1 + (i + 1) * MAX_BLOCKS)
    return t


def decode_multi(cfg, params, H, kc, vc, tokens, positions, tables, active, limit):
    B = len(tokens)
    keys, temps, top_ps, top_ks, want = greedy(B)
    return jax.jit(
        functools.partial(ModelRunner._decode_multi_impl, cfg, None, None, BS),
        static_argnums=(0,),
    )(
        H, params, kc, vc, jnp.asarray(tokens, jnp.int32),
        jnp.asarray(positions, jnp.int32), jnp.asarray(tables), keys, temps,
        top_ps, top_ks, want, jnp.asarray(active), jnp.asarray(limit, jnp.int32),
        jnp.zeros(B, jnp.int32), jnp.full((B, MAX_EOS_IDS), -1, jnp.int32),
    )


# ------------------------------------------- (a) the forward, every position


def test_full_forward_against_the_reference():
    """One sequence through the packed program alone: the logits at its last
    position, and the state and tail it leaves in its slot against the
    reference's loop (the state after the last token, the last three inputs
    of the convolution)."""
    cfg, params, d, layers, top = toy()
    n = 23
    prompt = prompt_tokens(n, 11)
    head, last = pack([prompt], tables_for(), 32)
    kc, vc = caches(cfg, fill=3.0)
    logits, kc, vc = jax.jit(functools.partial(M.prefill_packed, params, cfg))(
        *head, kc, vc, last, state_slots=jnp.asarray([1, 0, 0], jnp.int32)
    )
    want = np.asarray(R.forward(layers, top, d, [prompt], [n - 1]))[0, 0]
    assert rel(logits[0], want) < F32_TOL
    # the first layer is a Mamba layer: its slot against the reference's own
    # recurrence on the embedded prompt
    with jax.default_matmul_precision("highest"):
        x = top["embed"].astype(jnp.float32)[jnp.asarray(prompt)]
        xs, z, delta, b, c = R.scan_inputs(x, layers[0], d)
        _, states = R.recurrence(xs, delta, b, c, layers[0]["A_log"])
    np.testing.assert_allclose(np.asarray(kc[0][1]), np.asarray(states[-1]), atol=2e-5)
    assert np.all(np.asarray(kc[0][0]) == 3.0) and np.all(np.asarray(kc[0][2]) == 3.0)
    # the tail holds the inputs before the convolution: x W_in of the last 3
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + d["eps"])
    pre = jnp.matmul(h, layers[0]["w_in"].astype(jnp.float32), precision="highest")[:, : d["d_inner"]]
    np.testing.assert_allclose(
        np.asarray(vc[0][1]).reshape(3, -1), np.asarray(pre[-3:]), atol=2e-5
    )


# ------------------------- (b) two sequences in one pack, then decode_multi


@pytest.mark.parametrize("attn_impl,dtype,tol", [
    ("xla", "float32", F32_TOL), ("pallas_interpret", "float32", F32_TOL),
    ("xla", "bfloat16", BF16_TOL),
])
def test_packed_prefill_then_decode_through_slots_against_the_reference(attn_impl, dtype, tol):
    """Two prompts of unlike lengths packed into one prefill (the state is
    reset at the boundary, the convolution sees nothing of its neighbour),
    written to slots 2 and 0 of dirty slot arrays; then `decode_multi@H4`
    with lane 1 idle: the top-20 log-probs of every generated position
    against the reference's full pass."""
    dt = jnp.dtype(dtype)
    cfg, params, *_ = toy(attn_impl, dt)
    H, n0, n1 = 4, 13, 6
    prompts = [prompt_tokens(n0, 1), prompt_tokens(n1, 2)]
    tables = tables_for()
    lanes = [2, 0]  # the first prompt lives in lane 2, the second in lane 0
    head, last = pack(prompts, tables[lanes], 32)
    kc, vc = caches(cfg, dt, fill=5.0)
    logits, kc, vc = jax.jit(functools.partial(M.prefill_packed, params, cfg))(
        *head, kc, vc, last, state_slots=jnp.asarray(lanes + [0], jnp.int32)
    )
    want = [np.asarray(R.forward(*reference_weights()[1:], reference_weights()[0], [p], [len(p) - 1]))[0, 0] for p in prompts]
    assert rel(logits[0], want[0]) < tol and rel(logits[1], want[1]) < tol
    first = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
    packed, kc, vc = decode_multi(
        cfg, params, H, kc, vc, [first[1], 0, first[0]], [n1, 0, n0],
        tables, [True, False, True], [100] * LANES,
    )
    packed = np.asarray(packed)
    assert packed.shape[1] == LANES and (packed[:, 1, 0] == -1).all()
    K = (packed.shape[-1] - 2) // 2
    for lane, prompt, tok0 in ((2, prompts[0], first[0]), (0, prompts[1], first[1])):
        toks = packed[:, lane, 0].astype(np.int64).tolist()
        n = len(prompt)
        err = against_reference(
            [prompt + [int(tok0)] + toks], [n + h for h in range(H)],
            [[packed[h, lane, 2:2 + K] for h in range(H)]],
            [[packed[h, lane, 2 + K:] for h in range(H)]],
        )
        assert err < tol, (lane, err)
    # the idle lane's slot is as it was: a sequence in the middle of a
    # chunked prefill may own it
    assert np.all(np.asarray(kc[0][1]) == 5.0) and np.all(np.asarray(vc[0][1]) == 5.0)


# --------------------------------- (c) a prompt in two chunks, a mixed step


def chunk_args(tokens, start, total, table, slot, C):
    ctoks = np.zeros(C, np.int32)
    ctoks[:len(tokens)] = tokens
    return (
        jnp.asarray(ctoks), jnp.int32(start), jnp.int32(total),
        jnp.asarray(table), jnp.zeros(2, jnp.uint32), jnp.float32(0.0),
        jnp.float32(1.0), jnp.int32(0), jnp.bool_(True), jnp.float32(1.0),
        jnp.full(MAX_EOS_IDS, -1, jnp.int32), jnp.bool_(False), jnp.int32(slot),
    )


def test_a_prompt_prefilled_in_two_chunks_equals_one_pass():
    """A 13-token prompt enters lane 1 as an 8-token chunk and a 5-token
    one (its padded tail must not move the state), each in a mixed step on a
    batch whose lanes 0 and 2 decode: the chunk's first token and the lanes'
    tokens against the reference, and the slot the two chunks leave against
    the slot one packed pass leaves."""
    cfg, params, *_ = toy("xla")
    n, C, n_long = 9, 8, 13
    prompts = [prompt_tokens(n, 3), prompt_tokens(n, 4)]
    long_prompt = prompt_tokens(n_long, 5)
    tables = tables_for()
    head, last = pack(prompts, tables[[0, 2]], 32)
    kc, vc = caches(cfg, fill=2.0)
    logits, kc, vc = jax.jit(functools.partial(M.prefill_packed, params, cfg))(
        *head, kc, vc, last, state_slots=jnp.asarray([0, 2, 0], jnp.int32)
    )
    tok = np.zeros(LANES, np.int32)
    tok[[0, 2]] = np.asarray(jnp.argmax(logits, axis=-1), np.int32)[:2]
    keys, temps, top_ps, top_ks, want = greedy(LANES)
    mixed = jax.jit(functools.partial(ModelRunner._mixed_impl, cfg, None, None))
    sequences = {0: prompts[0] + [int(tok[0])], 2: prompts[1] + [int(tok[2])]}
    lane_ids, lane_lps = {0: [], 2: []}, {0: [], 2: []}
    chunk_out = None
    for step, start in enumerate((0, C)):
        chunk = chunk_args(long_prompt[start:start + C], start, n_long, tables[1], 1, C)
        positions = np.asarray([n + step, 0, n + step], np.int32)
        slots = tables[np.arange(LANES), positions // BS] * BS + positions % BS
        slots[1] = 0  # lane 1 does not decode: its write goes to the null block
        outs, kc, vc = mixed(
            params, kc, vc, (chunk,), jnp.asarray(tok), jnp.asarray(positions),
            jnp.asarray(tables), jnp.asarray(slots), keys, temps, top_ps, top_ks, want,
            jnp.full((LANES, MAX_EOS_IDS), -1, jnp.int32), jnp.zeros(LANES, bool),
        )
        chunk_out, (new, _, ids, lps) = outs[:4], outs[4:8]
        new = np.asarray(new, np.int32)
        for i in (0, 2):
            tok[i] = new[i]
            sequences[i].append(int(new[i]))
            lane_ids[i].append(np.asarray(ids[i]))
            lane_lps[i].append(np.asarray(lps[i]))
    for i in (0, 2):
        err = against_reference([sequences[i][:-1]], [n, n + 1], [lane_ids[i]], [lane_lps[i]])
        assert err < F32_TOL, (i, err)
    err = against_reference(
        [long_prompt], [n_long - 1], [[np.asarray(chunk_out[2])]], [[np.asarray(chunk_out[3])]],
    )
    assert err < F32_TOL, err
    # the same prompt in one packed pass, into a fresh lane
    head, last = pack([long_prompt], tables[[1]], 32)
    k1, v1 = caches(cfg)
    _, k1, v1 = jax.jit(functools.partial(M.prefill_packed, params, cfg))(
        *head, k1, v1, last, state_slots=jnp.asarray([1, 0, 0], jnp.int32)
    )
    for i in range(cfg.num_layers):
        if not cfg.is_attn_layer(i):
            np.testing.assert_allclose(np.asarray(kc[i][1]), np.asarray(k1[i][1]), atol=1e-5)
            np.testing.assert_allclose(np.asarray(vc[i][1]), np.asarray(v1[i][1]), atol=1e-5)


# ------------------------------ (d) a horizon against single steps, (e) reuse


def test_decode_multi_equals_single_steps_with_a_lane_that_ends_inside():
    """`decode_multi@H4` against four `decode` steps from the same caches:
    lane 0 may emit two tokens and then stops (it may leave anything in its
    slot), lane 2 runs all four. The same tokens; log-probs and lane 2's slot
    to float32 roundings (two programs order their sums differently)."""
    cfg, params, *_ = toy("xla")
    n = 10
    prompts = [prompt_tokens(n, 6), prompt_tokens(n, 7)]
    tables = tables_for()
    head, last = pack(prompts, tables[[0, 2]], 32)
    kc, vc = caches(cfg)
    logits, kc, vc = jax.jit(functools.partial(M.prefill_packed, params, cfg))(
        *head, kc, vc, last, state_slots=jnp.asarray([0, 2, 0], jnp.int32)
    )
    first = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
    H = 4
    packed, km, vm = decode_multi(
        cfg, params, H, kc, vc, [first[0], 0, first[1]], [n, 0, n], tables,
        [True, False, True], [2, 1, 100],
    )
    packed = np.asarray(packed)
    assert (packed[2:, 0, 0] == -1).all() and (packed[:2, 0, 0] >= 0).all()
    keys, temps, top_ps, top_ks, want = greedy(LANES)
    single = jax.jit(functools.partial(ModelRunner._decode_impl, cfg, None, None))
    tok = np.asarray([first[0], 0, first[1]], np.int32)
    pos = np.asarray([n, 0, n], np.int32)
    k1, v1 = kc, vc
    for h in range(H):
        live = np.asarray([h < 2, False, True])
        slots = np.where(live, tables[np.arange(LANES), pos // BS] * BS + pos % BS, 0)
        step_keys = keys.at[:, 1].add(jnp.uint32(h))
        (t, lp, _, _), k1, v1 = single(
            params, k1, v1, jnp.asarray(tok), jnp.asarray(pos), jnp.asarray(tables),
            jnp.asarray(slots.astype(np.int32)), step_keys, temps, top_ps, top_ks, want,
        )
        t, lp = np.asarray(t), np.asarray(lp)
        for lane in (0, 2):
            if live[lane]:
                assert packed[h, lane, 0] == t[lane]
                assert abs(packed[h, lane, 1] - lp[lane]) < 1e-5
                tok[lane], pos[lane] = t[lane], pos[lane] + 1
    for i in range(cfg.num_layers):
        if not cfg.is_attn_layer(i):
            # two programs: the compiler orders a product's sum differently
            # in the one and in the other (and by how many threads it has),
            # float32 roundings apart on values of order 1
            np.testing.assert_allclose(np.asarray(km[i][2]), np.asarray(k1[i][2]), atol=2e-5)
            np.testing.assert_allclose(np.asarray(vm[i][2]), np.asarray(v1[i][2]), atol=2e-5)


def test_a_reused_slot_gives_what_a_fresh_slot_gives():
    """Lane 1 serves one sequence (prefill and four decode steps), then a
    second one is prefilled into the same lane without any clearing: its
    logits and the four tokens behind them equal, bit for bit, what an
    untouched cache gives."""
    cfg, params, *_ = toy("xla")
    tables = tables_for()
    prefill = jax.jit(functools.partial(M.prefill_packed, params, cfg))

    def serve(kc, vc, prompt):
        head, last = pack([prompt], tables[[1]], 32)
        logits, kc, vc = prefill(*head, kc, vc, last, state_slots=jnp.asarray([1, 0, 0], jnp.int32))
        first = int(jnp.argmax(logits[0]))
        packed, kc, vc = decode_multi(
            cfg, params, 4, kc, vc, [0, first, 0], [0, len(prompt), 0], tables,
            [False, True, False], [100] * LANES,
        )
        return np.asarray(logits[0]), np.asarray(packed)[:, 1], kc, vc

    kc, vc = caches(cfg)
    _, _, kc, vc = serve(kc, vc, prompt_tokens(17, 8))
    second = prompt_tokens(9, 9)
    used_logits, used_steps, _, _ = serve(kc, vc, second)
    fresh_logits, fresh_steps, _, _ = serve(*caches(cfg), second)
    np.testing.assert_array_equal(used_logits, fresh_logits)
    np.testing.assert_array_equal(used_steps, fresh_steps)


# ------------------------------------------------ (f) both controls fail


def serve_one(cfg, params, seq, n_pre, blocks):
    """Logits of one sequence served as the cell serves it, in bfloat16: a
    packed prefill of its first `n_pre` tokens, then decode steps through
    the slot; rows n_pre - 1 to the end."""
    prefill = jax.jit(functools.partial(M.prefill_packed, params, cfg))
    single = jax.jit(functools.partial(M.decode, params, cfg))
    table = np.zeros((LANES, blocks), np.int32)
    table[0] = np.arange(1, blocks + 1)
    state, tail = (s for s, _ in cfg.state_kind().slot)
    paged = lambda: jnp.zeros((1, blocks + 1, BS, cfg.head_dim), jnp.bfloat16)
    kc = tuple(paged() if cfg.is_attn_layer(i) else jnp.zeros((LANES + 1,) + state, jnp.float32)
               for i in range(cfg.num_layers))
    vc = tuple(paged() if cfg.is_attn_layer(i) else jnp.zeros((LANES + 1,) + tail, jnp.float32)
               for i in range(cfg.num_layers))
    head, last = pack([seq[:n_pre]], table[[0]], -(-n_pre // 32) * 32)
    logits, kc, vc = prefill(*head, kc, vc, last, state_slots=jnp.asarray([0, 0, 0], jnp.int32))
    got = [np.asarray(logits[0], np.float32)]
    for p in range(n_pre, len(seq)):
        slot = table[0, p // BS] * BS + p % BS
        lg, kc, vc = single(
            jnp.asarray([seq[p], 0, 0], jnp.int32), jnp.asarray([p, 0, 0], jnp.int32),
            kc, vc, jnp.asarray(table), jnp.asarray([slot, 0, 0], jnp.int32),
        )
        got.append(np.asarray(lg[0], np.float32))
    return np.stack(got)


def test_both_controls_fail_the_toy_verdict_where_the_served_path_passes():
    """The cell's rule at the toy's size: a limit at least 1.5 times the
    served path's number (bfloat16 weights and activations, float32 state:
    what the configuration states) and at most two thirds of a control's
    (the reference in the program's place, one precision lower) exists for
    each control, so each fails the verdict the served path passes: int8
    weights on sequences of 28 tokens, and a bfloat16 state and scan on
    sequences of 1,200, because what a coarser state costs the logits grows
    with the tokens it has carried while what bfloat16 activations cost does
    not. The cell's check looks at 1,100 to 1,196 tokens for that reason
    (the configuration's `check.why` has the chip's readings). Served as
    the cell serves them (a packed prefill, then decode steps through the
    slot), the last 12 positions each, every id."""
    d, layers, top = reference_weights()
    cfg, params, *_ = toy("xla", jnp.bfloat16)

    def readings(n, seeds, lowers):
        seqs = [prompt_tokens(n, s) for s in seeds]
        rows = list(range(n - 12, n))
        want = np.asarray(R.forward(layers, top, d, seqs, rows))
        served = np.stack([serve_one(cfg, params, s, n - 11, -(-n // BS)) for s in seqs])
        out = {"served": rel(served, want)}
        for name in lowers:
            out[name] = rel(np.asarray(R.forward(layers, top, d, seqs, rows, lower=name)), want)
        return out

    short = readings(28, (20, 21, 22, 23), ("int8_weights", "bf16_state"))
    # the toy reads: served 0.019, int8 weights 0.044, bfloat16 state 0.007
    assert short["served"] < BF16_TOL
    assert 1.5 * short["served"] <= short["int8_weights"] * 2 / 3, short
    # on short sequences a bfloat16 state moves the logits by less than the
    # bfloat16 activations the configuration states already do: no limit on
    # these logits can fail it while the served path passes
    assert 0 < short["bf16_state"] < short["served"]
    long = readings(1200, (30, 31), ("bf16_state",))
    # the toy reads: served 0.02, bfloat16 state 0.05
    assert long["served"] < BF16_TOL
    assert long["bf16_state"] > 3 * short["bf16_state"]
    assert 1.5 * long["served"] <= long["bf16_state"] * 2 / 3, long
    # and the slot itself: the control's states are off by a hundred times
    # the tolerance that `test_full_forward_against_the_reference` holds the
    # program's slot to (2e-5)
    with jax.default_matmul_precision("highest"):
        x = top["embed"].astype(jnp.float32)[jnp.asarray(prompt_tokens(28, 20))]
        xs, z, delta, b, c = R.scan_inputs(x, layers[0], d)
        _, exact = R.recurrence(xs, delta, b, c, layers[0]["A_log"])
        _, lowered = R.recurrence(xs, delta, b, c, layers[0]["A_log"], lower="bf16_state")
    assert float(jnp.max(jnp.abs(exact[-1] - lowered[-1]))) > 100 * 2e-5


# ------------------------------------------------ (g) the family is found


def write_model_dir(path, hf=HF) -> str:
    from tests.util import make_test_tokenizer

    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(hf, f)
    make_test_tokenizer()._hf.save(os.path.join(path, "tokenizer.json"))
    return str(path)


def test_the_family_is_chosen_by_model_type_and_an_unknown_one_is_refused(tmp_path):
    cfg = config_from_model_dir(write_model_dir(tmp_path / "a"))
    assert isinstance(cfg, M.HybridSsmConfig)
    assert [cfg.is_attn_layer(i) for i in range(5)] == [False, True, False, False, True]
    kinds = layer_cache_kinds(cfg)
    assert [k.name for k in kinds] == ["recurrent", "kv_heads", "recurrent", "recurrent", "kv_heads"]
    assert recurrent_layers(cfg) == 3 and kinds[0].slot == (((8, 128), "float32"), ((384,), "float32"))
    assert kinds[0].slot_bytes == (8 * 128 + 384) * 4
    # the families the benchmark has answer with copies of what they declare
    dense = L.LlamaConfig.tiny()
    assert layer_cache_kinds(dense) == (layer_cache_kinds(dense)[0],) * dense.num_layers
    assert layer_cache_kinds(dense)[0].name == "kv_heads" and recurrent_layers(dense) == 0
    latent = mla_moe.MlaMoeConfig.tiny()
    assert [k.name for k in layer_cache_kinds(latent)] == ["latent"] * latent.num_layers
    for bad, words in (
        (dict(HF, num_experts=16), "num_experts"),
        (dict(HF, sliding_window=4096), "sliding_window"),
        (dict(HF, mamba_proj_bias=True), "mamba_proj_bias"),
    ):
        with pytest.raises(ValueError, match=words + ".*not implemented"):
            M.HybridSsmConfig.from_hf_dict(bad)
    for unknown in ("mamba", "falcon_h1", "rwkv"):
        with pytest.raises(ValueError, match=f"model_type '{unknown}' is not served"):
            config_from_model_dir(write_model_dir(tmp_path / unknown, dict(HF, model_type=unknown)))
    # a directory that names no model_type is still the grouped-query family's
    plain = {k: v for k, v in HF.items() if k != "model_type"}
    assert isinstance(config_from_model_dir(write_model_dir(tmp_path / "p", plain)), L.LlamaConfig)


def catalog_row() -> dict:
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        return next(r for r in map(json.loads, f) if r["name"] == "AI21-Jamba2-3B")


def test_the_catalog_rows_config_on_the_parents_rule_and_on_the_changes(tmp_path):
    """The parent handed any unknown `model_type` to `LlamaConfig`: this
    row's config became a dense 28-layer model of its widths (20 query heads
    over 1 key-value head, rope and all) under the row's name. The change
    builds the hybrid family's config from it, with 26 recurrent layers."""
    hf = catalog_row()["config"]
    parents = L.LlamaConfig.from_hf_dict(hf)  # the parent's rule, as it was
    assert parents.num_layers == 28 and parents.num_kv_heads == 1 and parents.rope_theta == 10000.0
    cfg = config_from_model_dir(write_model_dir(tmp_path, hf))
    assert isinstance(cfg, M.HybridSsmConfig)
    assert (cfg.d_inner, cfg.d_state, cfg.d_conv, cfg.dt_rank, cfg.head_dim) == (5120, 16, 4, 160, 128)
    assert [i for i in range(28) if cfg.is_attn_layer(i)] == [7, 21]
    assert recurrent_layers(cfg) == 26
    mamba, attn = M.mixer_param_counts(cfg)
    assert (mamba, attn) == (41_241_792, 13_762_560)
    assert M.param_count(cfg) == 26 * mamba + 2 * attn + 28 * (62_914_560 + 2 * 2560) + 167_772_160 + 2560
    assert 3.02e9 < M.param_count(cfg) < 3.04e9
    shapes = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == M.param_count(cfg)
    # a lane's slot: 26 x (5120 x 16 + 5120 x 3) float32 = 10.1 MB
    assert sum(k.slot_bytes for k in layer_cache_kinds(cfg)) == 26 * 5120 * 19 * 4 == 10_117_120


def test_the_programs_draw_is_the_references():
    """`init_params` draws a layer in one jitted program (a cold start on the
    chip compiled some 30 small ones before); the reference draws tensor by
    tensor from the same keys. Every matrix, bias and tap is the same to the
    bit; `A_log` and `b_dt`, float32 and computed through log and expm1,
    may differ in the last place where a fused program rounds once less."""
    cfg = M.HybridSsmConfig.from_hf_dict(HF)
    _, layers, top = reference_weights()
    mine = M.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)
    assert len(mine["layers"]) == len(layers) == cfg.num_layers
    for got, want in zip(mine["layers"], layers):
        assert set(got) == set(want)
        for name in got:
            a, b = (np.asarray(x.astype(jnp.float32)) for x in (got[name], want[name]))
            if name in ("A_log", "b_dt"):
                np.testing.assert_allclose(a, b, rtol=0, atol=2e-6)
            else:
                assert got[name].dtype == want[name].dtype or name.endswith("norm"), name
                np.testing.assert_array_equal(a, b, err_msg=name)
    np.testing.assert_array_equal(
        np.asarray(mine["embed"].astype(jnp.float32)), np.asarray(top["embed"].astype(jnp.float32)))


def test_block_budget_takes_the_state_slots_off_first(monkeypatch):
    from dynamo_tpu.engine.jax_engine import factory

    cfg = M.HybridSsmConfig.from_hf_dict(catalog_row()["config"])
    monkeypatch.setattr(factory, "hbm_budget_bytes", lambda: 16 * 2**30)
    # wanted: 64 lanes x 512 blocks + 64 = 32,832; a block is 2 layers' rows:
    # 2 x 16 x 2 x 128 x 2 = 16,384 bytes, so the pool is 0.54 GB and fits
    assert factory.default_num_blocks(cfg, 8192, 64) == 32_832
    # what fits when more is wanted than there is room for: the 0.85 budget
    # less 6.06 GB of weights less 65 slots of 10.1 MB, in blocks of 16 KiB
    want_all = factory.default_num_blocks(cfg, 262_144, 64)
    room = int(16 * 2**30 * 0.85) - 2 * M.param_count(cfg) - 65 * 10_117_120
    assert want_all == room // 16_384


@pytest.mark.parametrize("asked,words", [
    (dict(kv_dtype="int8"), "int8-resident cache"),
    (dict(quantize=True), "int8 weights"),
    (dict(meshed=True), "mesh"),
    (dict(fused_decode=True), "fused decode"),
    (dict(env={"DYN_KV_HOST_OFFLOAD_GB": "1"}), "block-manager tiers .*prefix reuse"),
    (dict(env={"DYN_SPEC_K": "3"}), "rejected draft would need the state rolled back"),
])
def test_what_a_recurrent_layer_does_not_support_is_refused_in_words(monkeypatch, asked, words):
    from dynamo_tpu.engine.jax_engine.factory import refuse_unsupported

    asked = dict(asked)
    for k, v in asked.pop("env", {}).items():
        monkeypatch.setenv(k, v)
    cfg, *_ = toy("xla")
    with pytest.raises(ValueError, match="recurrent state a sequence in 3 of its 5 layers.*" + words):
        refuse_unsupported(cfg, **asked)
    refuse_unsupported(L.LlamaConfig.tiny(), **asked)  # grouped-query: untouched
    monkeypatch.undo()
    refuse_unsupported(cfg)  # and nothing asked, nothing refused


async def test_the_factory_refuses_a_mesh_before_it_builds_anything(tmp_path):
    from dynamo_tpu.engine.jax_engine.factory import build_jax_engine

    with pytest.raises(ValueError, match="mesh"):
        await build_jax_engine(
            write_model_dir(tmp_path), kv_block_size=4, max_batch=2, num_blocks=16,
            tensor_parallel_size=2,
        )


def test_the_runner_allocates_by_layer_and_refuses_what_it_cannot_carry():
    cfg, params, *_ = toy("xla")
    kw = dict(num_blocks=NB, block_size=BS, max_batch=2, max_model_len=32, attn_impl="xla")
    with pytest.raises(ValueError, match="int8-resident"):
        ModelRunner(cfg, params, kv_dtype="int8", **kw)
    runner = ModelRunner(cfg, params, kv_dtype=jnp.float32, **kw)
    assert runner.state_slots == 3 and len(runner.k_cache) == len(runner.v_cache) == 5
    assert [tuple(a.shape) for a in runner.k_cache] == [
        (3, 8, 128), (1, NB, BS, 16), (3, 8, 128), (3, 8, 128), (1, NB, BS, 16)]
    assert [tuple(a.shape) for a in runner.v_cache] == [
        (3, 384), (1, NB, BS, 16), (3, 384), (3, 384), (1, NB, BS, 16)]
    assert runner.k_cache[0].dtype == jnp.float32 and runner.v_cache[0].dtype == jnp.float32
    for call in (
        lambda: runner.extract_blocks([1, 2]),
        lambda: runner.extract_blocks_tight([1]),
        lambda: runner.extract_blocks_device([1]),
        lambda: runner.inject_blocks([1], None, None),
        lambda: runner.inject_blocks_device([1], None, None),
    ):
        with pytest.raises(ValueError, match="3 of this model's 5 layers keep a recurrent state"):
            call()
    # a prefill must be told where the sequence lives
    with pytest.raises(ValueError, match="must name the lane slot"):
        runner.pack_prefill([])
    with pytest.raises(ValueError, match="must name the lane slot"):
        runner.prefill_chunk([3, 4], 0, 2, [1], 0.0, 1.0, 0)
    # a paged-only model's runner takes no slots and builds what it built
    dense = ModelRunner(L.LlamaConfig.tiny(), L.init_params(L.LlamaConfig.tiny(), jax.random.PRNGKey(0)), **kw)
    assert dense.state_slots == 0 and "state_slots" not in dense.pack_prefill([])


def test_checkpoint_names_round_trip_to_the_seeded_logits(tmp_path):
    """The seeded weights written under Hugging Face Jamba's checkpoint names
    and layouts (`A_log` `[d_inner, d_state]`, the convolution `[d_inner, 1,
    d_conv]`, matrices `[out, in]`) load back to the same logits."""
    from safetensors.numpy import save_file

    from dynamo_tpu.engine.jax_engine.weights import load_or_init_params

    cfg = dataclasses.replace(M.HybridSsmConfig.from_hf_dict(HF), attn_impl="xla")
    params = M.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    out: dict[str, np.ndarray] = {}

    def put(name, w, transpose=True):
        w = np.asarray(w, np.float32)
        out[name] = np.ascontiguousarray(w.T if transpose else w)

    mamba = {"w_in": "in_proj", "w_x": "x_proj", "w_dt": "dt_proj", "w_out": "out_proj"}
    for i, layer in enumerate(params["layers"]):
        p = f"model.layers.{i}."
        put(p + "input_layernorm.weight", layer["mix_norm"], False)
        put(p + "pre_ff_layernorm.weight", layer["mlp_norm"], False)
        for ours, theirs in (("wg", "gate"), ("wu", "up"), ("wd", "down")):
            put(f"{p}feed_forward.{theirs}_proj.weight", layer[ours])
        if cfg.is_attn_layer(i):
            for ours in ("wq", "wk", "wv", "wo"):
                put(f"{p}self_attn.{ours[1]}_proj.weight", layer[ours])
            continue
        for ours, theirs in mamba.items():
            put(f"{p}mamba.{theirs}.weight", layer[ours])
        out[p + "mamba.conv1d.weight"] = np.ascontiguousarray(
            np.asarray(layer["conv_w"], np.float32).T[:, None, :]
        )
        put(p + "mamba.conv1d.bias", layer["conv_b"], False)
        put(p + "mamba.dt_proj.bias", layer["b_dt"], False)
        put(p + "mamba.A_log", layer["A_log"])
        put(p + "mamba.D", layer["D"], False)
        for which in ("dt", "b", "c"):
            put(f"{p}mamba.{which}_layernorm.weight", layer[which + "_norm"], False)
    put("model.embed_tokens.weight", params["embed"], False)
    put("model.final_layernorm.weight", params["final_norm"], False)
    put("lm_head.weight", params["embed"], False)  # tied, written out again
    model_dir = write_model_dir(tmp_path)
    save_file(out, os.path.join(model_dir, "model.safetensors"))
    loaded = load_or_init_params(model_dir, cfg, dtype=jnp.float32)
    assert len(loaded["layers"]) == cfg.num_layers and "lm_head" not in loaded
    assert loaded["layers"][0]["A_log"].shape == (8, 128)
    prompt = prompt_tokens(12, 9)
    head, last = pack([prompt], tables_for()[[0]], 16)
    slots = jnp.asarray([0, 0, 0], jnp.int32)
    a, *_ = M.prefill_packed(params, cfg, *head, *caches(cfg), last, state_slots=slots)
    b, *_ = M.prefill_packed(loaded, cfg, *head, *caches(cfg), last, state_slots=slots)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="int8 weights"):
        load_or_init_params(model_dir, cfg, quantize=True)


# ------------------------------------------------------ (h) the engine


async def build(tmp_path, monkeypatch, **kw):
    from dynamo_tpu.engine.jax_engine.factory import build_jax_engine

    monkeypatch.setenv("DYN_DECODE_HORIZON", "4")
    engine, _ = await build_jax_engine(
        write_model_dir(tmp_path), name="t", kv_block_size=4, max_batch=4,
        **{"num_blocks": 96, **kw},
    )
    assert isinstance(engine.runner.config, M.HybridSsmConfig)
    return engine


async def test_served_through_the_engine_with_its_ledger_and_no_block_hashes(tmp_path, monkeypatch):
    """`build_jax_engine` on a `jamba` directory: the same engine, programs
    and cache manager. Two prompts (one chunked beside the other's decoding,
    at an 8-token step budget) stream exactly their tokens, alike in two
    runs; the ledger's `ssm` slot counts what the lane arrays said; no block
    hash is published; wiring disaggregation, a peer pull or tiers is
    refused in words."""
    from tests.test_colocated_disagg import collect_tokens

    monkeypatch.setenv("DYN_PREFILL_CHUNK_TOKENS", "8")
    engine = await build(tmp_path, monkeypatch)
    stored = []
    engine.on_blocks_stored = stored.extend
    try:
        short, long = list(range(3, 12)), [3 + (7 * i) % 40 for i in range(30)]
        first = await asyncio.gather(
            collect_tokens(engine, short, 12), collect_tokens(engine, long, 6)
        )
        again = await asyncio.gather(
            collect_tokens(engine, short, 12), collect_tokens(engine, long, 6)
        )
        assert first == again and [len(t) for t in first] == [12, 6]
        summary = engine.stats.goodput.summary()
        ssm = summary["ssm"]
        assert ssm["slot_resets"] == 4 and ssm["scan_tokens"] == 2 * (9 + 30)
        assert ssm["layer_steps"] > 0 and ssm["layer_steps"] % 3 == 0
        assert 0 < ssm["slots_live"] <= 4 * ssm["layer_steps"] // 3
        labels = set(summary["compile_s_by_label"])
        assert labels <= {"prefill_packed", "prefill_chunk", "mixed_step@c1", "mixed_step@c2",
                          "decode", "decode_multi@H4B4"}, labels
        bodies = {k: v["layer_bodies"] for k, v in summary["first_dispatch_by_label"].items()}
        assert all(bodies[k] == (4 if k.startswith("mixed") else 2) for k in bodies), bodies
        assert stored == []
        for wire in ("remote_prefill_client", "peer_block_client"):
            with pytest.raises(ValueError, match="keep a recurrent state"):
                setattr(engine, wire, object())
            setattr(engine, wire, None)
    finally:
        await engine.close()


async def test_a_preempted_sequence_replays_to_the_same_greedy_tokens(tmp_path, monkeypatch):
    """A sequence is preempted in the middle of its answer (its slot and
    blocks freed), another is served in between, and its replay from position
    0 (prompt and generated tokens through the prefill program, the state
    zeroed there) streams the tokens an undisturbed run streams."""
    from tests.test_colocated_disagg import collect_tokens

    engine = await build(tmp_path, monkeypatch)
    try:
        prompt = list(range(5, 19))
        undisturbed = await collect_tokens(engine, prompt, 24)

        async def preempt_once():
            while True:
                await asyncio.sleep(0.001)
                for seq in list(engine.slots):
                    if seq is not None and 6 <= seq.num_generated <= 16 and not seq.prefilling:
                        async with engine._device_lock:
                            if seq.slot is not None:
                                engine._preempt_seq(seq)
                                return

        task = asyncio.ensure_future(preempt_once())
        replayed = await collect_tokens(engine, prompt, 24)
        await task
        assert replayed == undisturbed and len(replayed) == 24
        assert engine.stats.goodput.summary()["tokens_wasted"].get("preempt_replay", 0) >= len(prompt) + 6
    finally:
        await engine.close()


# ------------------------------------------- (i) run in=http out=jax


def test_run_http_jax_streams_exact_token_counts(tmp_path):
    """`python -m dynamo_tpu.run in=http out=jax` on the toy directory, no
    option, variable or model name beyond what every model gets: streamed
    completions of exactly the tokens asked for, and `/debug/goodput` with
    the `ssm` slot."""
    import http.client
    import signal
    import socket
    import subprocess
    import time

    model_dir = write_model_dir(tmp_path / "m")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = {k: v for k, v in os.environ.items() if not k.startswith("DYN_")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, DYN_DECODE_HORIZON="4")
    log = open(tmp_path / "server.log", "w")
    proc = subprocess.Popen(
        [sys.executable, "-m", "dynamo_tpu.run", "in=http", "out=jax",
         "--model-path", model_dir, "--model-name", "toy", "--http-host", "127.0.0.1",
         "--http-port", str(port), "--context-length", "128", "--max-batch", "4"],
        env=env, cwd=REPO, stdout=log, stderr=subprocess.STDOUT,
    )
    try:
        deadline = time.monotonic() + 180
        while True:
            assert proc.poll() is None, open(tmp_path / "server.log").read()[-3000:]
            assert time.monotonic() < deadline, "server not ready"
            try:
                conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
                conn.request("GET", "/health")
                if conn.getresponse().status == 200:
                    break
            except OSError:
                pass
            time.sleep(0.5)
        from tests.util import make_test_tokenizer

        vocab = make_test_tokenizer()._hf.get_vocab()
        words = [w for w, i in sorted(vocab.items(), key=lambda kv: kv[1]) if i >= 3][:20]
        for n_out in (5, 17):
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
            body = json.dumps({
                "model": "toy", "prompt": " ".join(words[:12]), "max_tokens": n_out,
                "stream": True, "temperature": 0.0, "ignore_eos": True,
                "nvext": {"ignore_eos": True},
            })
            conn.request("POST", "/v1/completions", body, {"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 200
            usage, reasons = None, []
            for raw in resp.read().decode().splitlines():
                if raw.startswith("data: ") and raw != "data: [DONE]":
                    chunk = json.loads(raw[6:])
                    usage = chunk.get("usage") or usage
                    reasons += [c.get("finish_reason") for c in chunk.get("choices", []) if c.get("finish_reason")]
            assert reasons == ["length"]
            if usage is not None:
                assert usage["completion_tokens"] == n_out
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("GET", "/debug/goodput")
        ledger = json.loads(conn.getresponse().read())["goodput"]
        assert ledger["ssm"]["slot_resets"] == 2 and ledger["ssm"]["scan_tokens"] >= 24
        assert ledger["decode_tokens"] + 2 >= 5 + 17 - 2
    finally:
        proc.send_signal(signal.SIGINT)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
        log.close()
