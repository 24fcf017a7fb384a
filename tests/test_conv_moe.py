"""The short-convolution, sparse-expert family (`models/conv_moe.py`) at a toy
size of the same shape (two leading dense layers, a literal `layer_types`
that is not periodic with both kinds behind them, 8 experts, 4 a token, two
KV heads a cached row), held to the plain float32 reference of
`cellbench/reference/conv_moe.py` on logits; the tail slots (ONE array a
layer) through the runner and the engine; what the factory refuses for it;
its checkpoint names; and the rule that finds a family by its `model_type`.

Tolerances. In float32 (`torch_dtype` float32: the gated product and the
tail are then float32 too) the program and the reference compute the same
numbers in another order (a chunk's convolution from a carried tail against
one pass over the whole sequence; paged attention over paired heads against
per-head attention; grouped products against a loop over the experts): 2e-5
of the logits' spread is ten times what such runs read (1e-6) and a
thousandth of the smallest difference a wrong form makes (a tail not zeroed
at a pack's boundary, a neighbour's half of a paired row, the bias in the
weights read 1e-2 and more). In bfloat16 the toy reads 0.02 to 0.06 (a score
near the top-4 cut of 8 experts flips an expert now and then); 0.1 holds it
to the same order. Where two of the program's own forms are compared
the arithmetic is the same: a reused slot against a fresh one runs one
program twice and must be equal to the last bit.
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
from cellbench.reference import conv_moe as R  # noqa: E402
from dynamo_tpu.engine.jax_engine.model_runner import ModelRunner  # noqa: E402
from dynamo_tpu.models import (  # noqa: E402
    config_from_model_dir, layer_cache_kinds, recurrent_layers,
)
from dynamo_tpu.models import conv_moe as M  # noqa: E402
from dynamo_tpu.ops.sampling import MAX_EOS_IDS  # noqa: E402
# the toy harness of the other family with slots: the same block size, lanes,
# tables and vocabulary, so its packing, its greedy lanes, its horizon call and
# its compared number serve here as they are
from tests.test_hybrid_ssm import (  # noqa: E402
    BS, LANES, MAX_BLOCKS, NB, chunk_args, decode_multi, greedy, pack,
    prompt_tokens, rel, tables_for,
)
from tests.test_hybrid_ssm import write_model_dir as _write_model_dir  # noqa: E402

HF = {
    "model_type": "lfm2_moe", "hidden_size": 64, "intermediate_size": 160,
    "moe_intermediate_size": 32, "num_hidden_layers": 6,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "full_attention"],
    "num_dense_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
    "conv_L_cache": 3, "conv_bias": False, "num_experts": 8,
    "num_experts_per_tok": 4, "norm_topk_prob": True, "use_expert_bias": True,
    "routed_scaling_factor": 1, "rope_theta": 10000.0, "norm_eps": 1e-5,
    "vocab_size": 300, "max_position_embeddings": 128,
    "tie_word_embeddings": True, "torch_dtype": "float32",
}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
assert (BS, NB, MAX_BLOCKS, LANES) == (4, 48, 8, 3)
F32_TOL, BF16_TOL = 2e-5, 0.1
CONV_LAYERS = (0, 1, 3, 4)


@functools.lru_cache(maxsize=None)
def reference_weights():
    d = R.dims(HF)
    *layers, top = list(R.seeded_layers(d, 0))
    return d, layers, top


def toy(attn_impl: str = "xla", dtype=jnp.float32):
    """(config, params handed over from the reference's own draw, the
    reference's dims, layers and top)."""
    hf = dict(HF, torch_dtype=jnp.dtype(dtype).name)
    cfg = dataclasses.replace(M.ConvMoeConfig.from_hf_dict(hf), attn_impl=attn_impl)
    d, layers, top = reference_weights()
    params = {
        "layers": [
            {k: v.astype(jnp.float32 if k == "router_bias" else dtype) for k, v in l.items()}
            for l in layers
        ],
        "embed": top["embed"].astype(dtype),
        "final_norm": top["final_norm"].astype(dtype),
    }
    return cfg, params, d, layers, top


def caches(cfg, dtype=jnp.float32, fill: float = 0.0):
    """The runner's two containers for LANES lanes and the null lane: pages
    (two KV heads a row) for an attention layer; for a convolution layer the
    tail array filled with `fill` (a slot's content before a sequence starts
    must not count) where the keys ride and None where the values would."""
    ((tail, _),) = cfg.tail_kind().slot
    pages = lambda: jnp.zeros(
        (cfg.num_kv_heads // cfg.kv_pack, NB, BS, cfg.kv_pack * cfg.head_dim), dtype)
    k = tuple(
        pages() if cfg.is_attn_layer(i)
        else jnp.full((LANES + 1,) + tail, fill, jnp.dtype(cfg.conv_dtype))
        for i in range(cfg.num_layers)
    )
    v = tuple(pages() if cfg.is_attn_layer(i) else None for i in range(cfg.num_layers))
    return k, v


def reference_logits(sequences, rows, lower=None):
    d, layers, top = reference_weights()
    return np.asarray(R.forward(layers, top, d, sequences, rows, lower=lower))


def against_reference(sequences, rows, top_ids, top_lps):
    want = reference_logits(sequences, rows)
    served, reference, stds = [], [], []
    for i in range(len(sequences)):
        for r in range(len(rows)):
            ids = np.asarray(top_ids[i][r], np.int64)
            served.append([float(x) for x in top_lps[i][r]])
            reference.append([float(x) for x in want[i, r, ids]])
            stds.append(float(np.std(want[i, r])))
    return logit_error(served, reference, stds)["rms_rel"]


def slots_of(n: int, lanes: list[int]):
    return jnp.asarray(lanes + [0] * (n - len(lanes)), jnp.int32)


# ------------------------------------------- (a) the forward, every position


def test_full_forward_against_the_reference():
    """One sequence through the packed program alone: the logits at its last
    position, and the tail it leaves in its slot against the reference's own
    gated product on the embedded prompt (its last two rows, oldest first);
    no other lane's slot is touched."""
    cfg, params, d, layers, top = toy()
    n = 23
    prompt = prompt_tokens(n, 11)
    head, last = pack([prompt], tables_for(), 32)
    kc, vc = caches(cfg, fill=3.0)
    logits, kc, vc = jax.jit(functools.partial(M.prefill_packed, params, cfg))(
        *head, kc, vc, last, state_slots=slots_of(LANES, [1])
    )
    assert rel(logits[0], reference_logits([prompt], [n - 1])[0, 0]) < F32_TOL
    assert all(vc[i] is None for i in CONV_LAYERS)
    with jax.default_matmul_precision("highest"):
        x = top["embed"].astype(jnp.float32)[jnp.asarray(prompt)]
        g, _ = R.gated_input(x, layers[0], d)
    np.testing.assert_allclose(
        np.asarray(kc[0][1]).reshape(2, -1), np.asarray(g[-2:]), atol=2e-5
    )
    assert np.all(np.asarray(kc[0][0]) == 3.0) and np.all(np.asarray(kc[0][2]) == 3.0)


# ------------------------- (b) two sequences in one pack, then decode_multi


@pytest.mark.parametrize("attn_impl,dtype,tol", [
    ("xla", "float32", F32_TOL), ("pallas_interpret", "float32", F32_TOL),
    ("xla", "bfloat16", BF16_TOL), ("pallas_interpret", "bfloat16", BF16_TOL),
])
def test_packed_prefill_then_decode_through_slots_against_the_reference(attn_impl, dtype, tol):
    """Two prompts of unlike lengths packed into one prefill (the tail is
    zeroed at the boundary: the convolution sees nothing of its neighbour),
    written to slots 2 and 0 of dirty slot arrays; then `decode_multi@H4`
    with lane 1 idle: the top-20 log-probs of every generated position
    against the reference's full pass, through the paged kernel over paired
    heads and through the XLA form alike; and the experts' counters that ride
    the same fetch."""
    dt = jnp.dtype(dtype)
    cfg, params, *_ = toy(attn_impl, dt)
    H, n0, n1 = 4, 13, 6
    prompts = [prompt_tokens(n0, 1), prompt_tokens(n1, 2)]
    tables = tables_for()
    lanes = [2, 0]  # the first prompt lives in lane 2, the second in lane 0
    head, last = pack(prompts, tables[lanes], 32)
    kc, vc = caches(cfg, dt, fill=5.0)
    logits, kc, vc = jax.jit(functools.partial(M.prefill_packed, params, cfg))(
        *head, kc, vc, last, state_slots=slots_of(LANES, lanes)
    )
    for i, p in enumerate(prompts):
        assert rel(logits[i], reference_logits([p], [len(p) - 1])[0, 0]) < tol
    first = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
    packed, kc, vc = decode_multi(
        cfg, params, H, kc, vc, [first[1], 0, first[0]], [n1, 0, n0],
        tables, [True, False, True], [100] * LANES,
    )
    packed = np.asarray(packed)
    # a row behind the lanes carries what the expert layers counted
    assert packed.shape[1] == LANES + 1 and (packed[:, 1, 0] == -1).all()
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
    assert np.all(np.asarray(kc[0][1], np.float32) == 5.0)
    # 4 expert layers x 4 steps; 2 live lanes x 4 experts each; an idle lane
    # is given to no expert
    counted = packed[:, -1, :4].sum(0)
    assert counted[0] == 4 * H and counted[1] == 4 * H * 2 * 4
    assert 4 * H * 4 <= counted[2] <= 4 * H * 8 and 4 * H <= counted[3] <= 4 * H * 2


# --------------------------------- (c) a prompt in three chunks, mixed steps


@pytest.mark.parametrize("attn_impl", ["xla", "pallas_interpret"])
def test_a_prompt_prefilled_in_three_chunks_equals_one_pass(attn_impl):
    """A 21-token prompt enters lane 1 as chunks of 8, 8 and 5 tokens (two
    chunk boundaries for the tail to cross in its slot; the last chunk's
    padded rows must not move it), each in a mixed step on a batch whose
    lanes 0 and 2 decode: the chunk's first token and the lanes' tokens
    against the reference, and the slot the three chunks leave against the
    slot one packed pass leaves."""
    cfg, params, *_ = toy(attn_impl)
    n, C, n_long = 9, 8, 21
    prompts = [prompt_tokens(n, 3), prompt_tokens(n, 4)]
    long_prompt = prompt_tokens(n_long, 5)
    tables = tables_for()
    head, last = pack(prompts, tables[[0, 2]], 32)
    kc, vc = caches(cfg, fill=2.0)
    logits, kc, vc = jax.jit(functools.partial(M.prefill_packed, params, cfg))(
        *head, kc, vc, last, state_slots=slots_of(LANES, [0, 2])
    )
    tok = np.zeros(LANES, np.int32)
    tok[[0, 2]] = np.asarray(jnp.argmax(logits, axis=-1), np.int32)[:2]
    keys, temps, top_ps, top_ks, want = greedy(LANES)
    mixed = jax.jit(functools.partial(ModelRunner._mixed_impl, cfg, None, None))
    sequences = {0: prompts[0] + [int(tok[0])], 2: prompts[1] + [int(tok[2])]}
    lane_ids, lane_lps = {0: [], 2: []}, {0: [], 2: []}
    chunk_out = None
    starts = (0, C, 2 * C)
    for step, start in enumerate(starts):
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
    rows = [n + s for s in range(len(starts))]
    for i in (0, 2):
        err = against_reference([sequences[i][:-1]], rows, [lane_ids[i]], [lane_lps[i]])
        assert err < F32_TOL, (i, err)
    err = against_reference(
        [long_prompt], [n_long - 1], [[np.asarray(chunk_out[2])]], [[np.asarray(chunk_out[3])]],
    )
    assert err < F32_TOL, err
    # the same prompt in one packed pass, into a fresh lane
    head, last = pack([long_prompt], tables[[1]], 32)
    k1, v1 = caches(cfg)
    _, k1, v1 = jax.jit(functools.partial(M.prefill_packed, params, cfg))(
        *head, k1, v1, last, state_slots=slots_of(LANES, [1])
    )
    for i in CONV_LAYERS:
        np.testing.assert_allclose(np.asarray(kc[i][1]), np.asarray(k1[i][1]), atol=1e-5)


@pytest.mark.parametrize("attn_impl", ["xla", "pallas_interpret"])
def test_a_whole_prompt_through_the_flash_prefill_kernel(attn_impl):
    """`prefill` (one whole prompt padded to a bucket): the attention layers
    through the flash prefill kernel on keys and values handed over as their
    stored rows, against the reference and against the packed program's
    slot and pages."""
    cfg, params, *_ = toy(attn_impl)
    n = 27
    prompt = prompt_tokens(n, 12)
    tokens = np.zeros(32, np.int32)
    tokens[:n] = prompt
    table = tables_for()[1]
    kc, vc = caches(cfg, fill=4.0)
    logits, kc, vc = jax.jit(functools.partial(M.prefill, params, cfg))(
        jnp.asarray(tokens), jnp.int32(n), kc, vc, jnp.asarray(table),
        state_slots=jnp.int32(1),
    )
    assert rel(logits, reference_logits([prompt], [n - 1])[0, 0]) < F32_TOL
    head, last = pack([prompt], tables_for()[[1]], 32)
    k1, v1 = caches(cfg, fill=4.0)
    _, k1, v1 = jax.jit(functools.partial(M.prefill_packed, params, cfg))(
        *head, k1, v1, last, state_slots=slots_of(LANES, [1])
    )
    live = np.asarray(table[: -(-n // BS)])
    for i in range(cfg.num_layers):
        if cfg.is_attn_layer(i):
            np.testing.assert_allclose(
                np.asarray(kc[i][:, live]).reshape(cfg.num_kv_heads // cfg.kv_pack, -1, 32)[:, :n],
                np.asarray(k1[i][:, live]).reshape(cfg.num_kv_heads // cfg.kv_pack, -1, 32)[:, :n],
                atol=1e-5)
        else:
            np.testing.assert_allclose(np.asarray(kc[i][1]), np.asarray(k1[i][1]), atol=1e-5)
            assert np.all(np.asarray(kc[i][0]) == 4.0)


# ------------------------------ (d) a horizon against single steps, (e) reuse


def test_decode_multi_equals_single_steps_with_a_lane_that_ends_inside():
    """`decode_multi@H4` against four `decode` steps from the same caches:
    lane 0 may emit two tokens and then stops, lane 2 runs all four. The
    same tokens; log-probs and lane 2's tail to float32 roundings."""
    cfg, params, *_ = toy("xla")
    n = 10
    prompts = [prompt_tokens(n, 6), prompt_tokens(n, 7)]
    tables = tables_for()
    head, last = pack(prompts, tables[[0, 2]], 32)
    kc, vc = caches(cfg)
    logits, kc, vc = jax.jit(functools.partial(M.prefill_packed, params, cfg))(
        *head, kc, vc, last, state_slots=slots_of(LANES, [0, 2])
    )
    first = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
    H = 4
    packed, km, _ = decode_multi(
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
    for i in CONV_LAYERS:
        np.testing.assert_allclose(np.asarray(km[i][2]), np.asarray(k1[i][2]), atol=2e-5)


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
        logits, kc, vc = prefill(*head, kc, vc, last, state_slots=slots_of(LANES, [1]))
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
    the slot and the pages; rows n_pre - 1 to the end."""
    prefill = jax.jit(functools.partial(M.prefill_packed, params, cfg))
    single = jax.jit(functools.partial(M.decode, params, cfg))
    table = np.zeros((LANES, blocks), np.int32)
    table[0] = np.arange(1, blocks + 1)
    ((tail, _),) = cfg.tail_kind().slot
    paged = lambda: jnp.zeros(
        (cfg.num_kv_heads // cfg.kv_pack, blocks + 1, BS, cfg.kv_pack * cfg.head_dim), jnp.bfloat16)
    kc = tuple(paged() if cfg.is_attn_layer(i) else jnp.zeros((LANES + 1,) + tail, jnp.bfloat16)
               for i in range(cfg.num_layers))
    vc = tuple(paged() if cfg.is_attn_layer(i) else None for i in range(cfg.num_layers))
    head, last = pack([seq[:n_pre]], table[[0]], -(-n_pre // 32) * 32)
    logits, kc, vc = prefill(*head, kc, vc, last, state_slots=slots_of(LANES, [0]))
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
    """The cell's rule at the toy's size: a limit with a fifth of room on
    both sides (1.2 times the served path's number, 0.8 of a control's)
    exists for each control, so each fails the verdict the served path
    passes (bfloat16 weights, activations, gated product and tail: what the
    configuration states; a control is the reference in the program's place,
    one precision lower): int8 weights, and the gated product (what the tail
    keeps) in an 8-bit float. The toy's eight experts of 32 leave bfloat16 a
    wider margin than the published widths do (a score near the top-4 cut
    flips an expert): the cell's own readings are in its configuration's
    `check.why`. Served as the cell serves them (a packed prefill, then
    decode steps through slot and pages), the last 12 positions each, every
    id. And the tail itself: the control's is off by a hundred times what the
    program's slot is held to."""
    d, layers, top = reference_weights()
    cfg, params, *_ = toy("xla", jnp.bfloat16)
    n, seeds = 28, (20, 21, 22, 23)
    seqs = [prompt_tokens(n, s) for s in seeds]
    rows = list(range(n - 12, n))
    want = reference_logits(seqs, rows)
    served = rel(np.stack([serve_one(cfg, params, s, n - 11, -(-n // BS)) for s in seqs]), want)
    controls = {
        name: rel(reference_logits(seqs, rows, lower=name), want)
        for name in ("int8_weights", "fp8_conv")
    }
    # the toy reads: served 0.033, int8 weights 0.068, 8-bit gated product 0.11
    assert served < BF16_TOL
    for name, reading in controls.items():
        assert 1.2 * served <= 0.8 * reading, (name, served, reading)
    with jax.default_matmul_precision("highest"):
        x = top["embed"].astype(jnp.float32)[jnp.asarray(seqs[0])]
        exact, _ = R.gated_input(x, layers[0], d)
        lowered, _ = R.gated_input(x, layers[0], d, lower="fp8_conv")
    assert float(jnp.max(jnp.abs(exact[-2:] - lowered[-2:]))) > 100 * 2e-5


# ------------------------------------------------ (g) the family is found


def write_model_dir(path, hf=HF) -> str:
    return _write_model_dir(path, hf)


def test_the_family_is_chosen_by_model_type_and_what_it_lacks_is_refused(tmp_path):
    cfg = config_from_model_dir(write_model_dir(tmp_path / "a"))
    assert isinstance(cfg, M.ConvMoeConfig) and cfg.conv_dtype == "float32"
    assert [cfg.is_attn_layer(i) for i in range(6)] == [False, False, True, False, False, True]
    assert [cfg.is_moe_layer(i) for i in range(6)] == [False, False, True, True, True, True]
    kinds = layer_cache_kinds(cfg)
    assert [k.name for k in kinds] == [
        "recurrent", "recurrent", "kv_heads", "recurrent", "recurrent", "kv_heads"]
    # a slot of ONE array: the last two gated inputs, flat
    assert recurrent_layers(cfg) == 4 and kinds[0].slot == (((128,), "float32"),)
    assert kinds[0].slot_bytes == 128 * 4
    # two KV heads of 16 a stored row of 32: the same values a token
    assert (kinds[2].heads, kinds[2].stored_width, kinds[2].pack) == (1, 32, 2)
    assert kinds[2].stored_values_per_token() == 2 * 2 * 16
    for bad, words in (
        (dict(HF, conv_bias=True), "conv_bias"),
        (dict(HF, rope_scaling={"rope_type": "linear", "factor": 2}), "rope_scaling"),
        (dict(HF, layer_types=HF["layer_types"][:5]), "layer_types"),
        (dict(HF, layer_types=["conv"] * 5 + ["sliding_attention"]), "layer_types"),
        (dict(HF, layer_types=["conv"] * 6), "layer_types"),
        (dict(HF, num_experts=1), "num_experts"),
        (dict(HF, torch_dtype="float16"), "torch_dtype"),
    ):
        with pytest.raises(ValueError, match=words + ".*not implemented"):
            M.ConvMoeConfig.from_hf_dict(bad)
    for unknown in ("lfm2", "lfm2_vl", "mamba"):
        with pytest.raises(ValueError, match=f"model_type '{unknown}' is not served.*lfm2_moe"):
            config_from_model_dir(write_model_dir(tmp_path / unknown, dict(HF, model_type=unknown)))
    for call in (M.prefill_mm, M.prefill_context_parallel, M.embed_pooled, M.decode_verify):
        with pytest.raises(NotImplementedError, match="short-convolution family"):
            call()


def catalog_row() -> dict:
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        return next(r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B")


def test_the_catalog_rows_config_whole_and_cut(tmp_path):
    """The parent refused `lfm2_moe` in words; the change builds the
    family's config from the catalog row's, at the widths published: 18
    convolution layers and 6 attention layers, 8,339,930,560 parameters; the
    benchmark's cut (its first 16 layers) holds 5,399,129,024."""
    hf = catalog_row()["config"]
    cfg = config_from_model_dir(write_model_dir(tmp_path, hf))
    assert isinstance(cfg, M.ConvMoeConfig)
    assert (cfg.head_dim, cfg.kv_pack, cfg.conv_L_cache, cfg.rms_eps) == (64, 2, 3, 1e-5)
    assert [i for i in range(24) if cfg.is_attn_layer(i)] == [2, 6, 10, 14, 18, 21]
    assert recurrent_layers(cfg) == 18 and cfg.tie_word_embeddings
    conv, attn = M.mixer_param_counts(cfg)
    assert (conv, attn) == (16_783_360, 10_485_888)
    assert M.routed_ffn_params(cfg) == 352_387_104
    assert M.param_count(cfg) == 8_339_930_560
    assert M.expert_param_count(cfg) == 22 * 32 * 11_010_048
    with open(os.path.join(REPO, "cellbench", "configs", "lfm2-8b-a1b-bf16-l16.json")) as f:
        cut = M.ConvMoeConfig.from_hf_dict(json.load(f))
    assert cut.num_layers == 16 and cut.layer_types == tuple(hf["layer_types"][:16])
    assert M.param_count(cut) == 5_399_129_024
    shapes = jax.eval_shape(lambda: M.init_params(cut, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == M.param_count(cut)
    kinds = layer_cache_kinds(cut)
    # a lane's slot: 12 x 2 x 2048 bfloat16 = 98 KB; a token's rows: 8,192 B
    assert sum(k.slot_bytes for k in kinds) == 12 * 2 * 2048 * 2
    assert sum(k.stored_values_per_token() * 2 for k in kinds) == 4 * 2 * 8 * 64 * 2 == 8192
    assert (kinds[2].heads, kinds[2].stored_width) == (4, 128)


def test_the_programs_draw_is_the_references():
    """`init_params` draws a layer in one jitted program; the reference draws
    tensor by tensor from the same keys. Every tensor is the same to the bit
    (`_dense` keeps the draw and its divisor behind a barrier, where a
    compiler would otherwise fold constants and round one value in ten
    thousand differently)."""
    cfg = M.ConvMoeConfig.from_hf_dict(HF)
    _, layers, top = reference_weights()
    mine = M.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)
    assert len(mine["layers"]) == len(layers) == cfg.num_layers
    for got, want in zip(mine["layers"], layers):
        assert set(got) == set(want)
        for name in got:
            a, b = (np.asarray(x.astype(jnp.float32)) for x in (got[name], want[name]))
            np.testing.assert_array_equal(a, b, err_msg=name)
    np.testing.assert_array_equal(
        np.asarray(mine["embed"].astype(jnp.float32)), np.asarray(top["embed"].astype(jnp.float32)))
    assert "lm_head" not in mine


def test_the_fixed_routing_patch_scales_both_draws_and_nothing_else():
    """`benchmarks/fixed_routing/sitecustomize.py` on a child's PYTHONPATH
    (what `benchmarks/conv_moe_check_lab.py --fixed-routing` does to the
    server and to the reference): both makers draw `expert_bias` that many
    times larger and every other tensor as before, the two draws stay each
    other's to the bit, and every token of a layer then chooses the four
    experts of the largest bias whatever its scores are."""
    import subprocess

    code = (
        "import json, sys, jax, jax.numpy as jnp, numpy as np\n"
        "sys.path.insert(0, %r)\n"
        "from tests.test_conv_moe import HF\n"
        "from cellbench.reference import conv_moe as R\n"
        "from dynamo_tpu.models import conv_moe as M\n"
        "d = R.dims(HF); layers = list(R.seeded_layers(d, 0))[:-1]\n"
        "mine = M.init_params(M.ConvMoeConfig.from_hf_dict(HF), jax.random.PRNGKey(0), jnp.bfloat16)\n"
        "same = all(np.array_equal(np.asarray(a[k].astype(jnp.float32)), np.asarray(b[k].astype(jnp.float32)))\n"
        "           for a, b in zip(mine['layers'], layers) for k in b)\n"
        "h = jax.random.normal(jax.random.PRNGKey(1), (50, d['hidden']))\n"
        "chosen = [sorted({tuple(sorted(r)) for r in np.asarray(R.route(h, l, d)[0]).tolist()})\n"
        "          for l in layers if 'router' in l]\n"
        "print(json.dumps({'scales': [R.EXPERT_BIAS_SCALE, M.EXPERT_BIAS_SCALE], 'same': same,\n"
        "                  'bias': [float(jnp.std(l['router_bias'])) for l in layers if 'router' in l],\n"
        "                  'wg': float(jnp.std(layers[2]['wg'].astype(jnp.float32))),\n"
        "                  'sets_a_layer': [len(c) for c in chosen]}))\n"
    ) % REPO

    def child(scale):
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
            [os.path.join(REPO, "benchmarks", "fixed_routing"), REPO]))
        env.pop("CONV_MOE_EXPERT_BIAS_SCALE", None)
        if scale:
            env["CONV_MOE_EXPERT_BIAS_SCALE"] = scale
        out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                             capture_output=True, text=True, timeout=170)
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    plain, fixed = child(None), child("100")
    assert plain["scales"] == [0.01, 0.01] and fixed["scales"] == [100.0, 100.0]
    assert plain["same"] and fixed["same"] and plain["wg"] == fixed["wg"]
    for a, b in zip(plain["bias"], fixed["bias"]):
        assert b == pytest.approx(a * 1e4, rel=1e-5)
    # one set of four a layer where the bias decides; many where the scores do
    assert set(fixed["sets_a_layer"]) == {1} and min(plain["sets_a_layer"]) > 5


def test_block_budget_takes_the_tail_slots_off_first(monkeypatch):
    from dynamo_tpu.engine.jax_engine import factory

    with open(os.path.join(REPO, "cellbench", "configs", "lfm2-8b-a1b-bf16-l16.json")) as f:
        cfg = M.ConvMoeConfig.from_hf_dict(json.load(f))
    monkeypatch.setattr(factory, "hbm_budget_bytes", lambda: 16 * 2**30)
    # wanted: 64 lanes x 512 blocks + 64 = 32,832 blocks of 4 layers' rows
    # (4 x 16 x 2 x 8 x 64 x 2 = 131,072 bytes); beside 10.8 GB of weights
    # the 0.85 budget holds fewer: what is left after the 65 slots of 98 KB
    room = int(16 * 2**30 * 0.85) - 2 * M.param_count(cfg) - 65 * 12 * 4096 * 2
    assert factory.default_num_blocks(cfg, 8192, 64) == room // 131_072 < 32_832
    assert factory.default_num_blocks(cfg, 512, 8) == 8 * 32 + 64


@pytest.mark.parametrize("asked,words", [
    (dict(kv_dtype="int8"), "int8-resident cache"),
    (dict(quantize=True), "int8 weights.*short convolutions and expert stacks"),
    (dict(meshed=True), "mesh"),
    (dict(fused_decode=True), "fused decode"),
    (dict(env={"DYN_KV_HOST_OFFLOAD_GB": "1"}), "block-manager tiers .*prefix reuse"),
    (dict(env={"DYN_SPEC_K": "3"}), "rejected draft would need the state rolled back"),
])
def test_what_the_family_is_not_served_with_is_refused_in_words(monkeypatch, asked, words):
    from dynamo_tpu.engine.jax_engine.factory import refuse_unsupported

    asked = dict(asked)
    for k, v in asked.pop("env", {}).items():
        monkeypatch.setenv(k, v)
    cfg, *_ = toy("xla")
    with pytest.raises(ValueError, match="recurrent state a sequence in 4 of its 6 layers.*" + words):
        refuse_unsupported(cfg, **asked)
    monkeypatch.undo()
    refuse_unsupported(cfg)  # nothing asked, nothing refused


def test_the_runner_allocates_a_slot_of_one_array_and_refuses_what_it_cannot_carry():
    cfg, params, *_ = toy("xla")
    kw = dict(num_blocks=NB, block_size=BS, max_batch=2, max_model_len=32, attn_impl="xla")
    with pytest.raises(ValueError, match="int8-resident"):
        ModelRunner(cfg, params, kv_dtype="int8", **kw)
    runner = ModelRunner(cfg, params, kv_dtype=jnp.float32, **kw)
    assert runner.state_slots == 3 and len(runner.k_cache) == len(runner.v_cache) == 6
    assert [tuple(a.shape) for a in runner.k_cache] == [
        (3, 128), (3, 128), (1, NB, BS, 32), (3, 128), (3, 128), (1, NB, BS, 32)]
    # nothing stands in for a second array: no leaf, nothing donated
    assert [None if a is None else tuple(a.shape) for a in runner.v_cache] == [
        None, None, (1, NB, BS, 32), None, None, (1, NB, BS, 32)]
    assert len(jax.tree.leaves(runner.v_cache)) == 2
    assert runner.k_cache[0].dtype == jnp.float32
    for call in (
        lambda: runner.extract_blocks([1, 2]),
        lambda: runner.inject_blocks([1], None, None),
    ):
        with pytest.raises(ValueError, match="4 of this model's 6 layers keep a recurrent state"):
            call()
    with pytest.raises(ValueError, match="must name the lane slot"):
        runner.pack_prefill([])
    # the published dtype: a bfloat16 tail
    bf16 = dataclasses.replace(cfg, conv_dtype="bfloat16")
    assert ModelRunner(bf16, params, **kw).k_cache[0].dtype == jnp.bfloat16
    # on the chip the kernel's tiling is asked of the stored row: 64-wide
    # heads in pairs pass where a head a row would be refused
    from dynamo_tpu.ops.attention import _pallas_tileable

    wide = layer_cache_kinds(dataclasses.replace(cfg, head_dim=64, num_kv_heads=8))[2]
    assert wide.stored_width == 128 and _pallas_tileable(wide.stored_width, 16)
    assert not _pallas_tileable(64, 16)


def test_checkpoint_names_round_trip_to_the_seeded_logits(tmp_path):
    """The seeded weights written under the names and layouts of Hugging
    Face's `Lfm2Moe*` classes (matrices `[out, in]`, the convolution
    `[hidden, 1, conv_L_cache]`, `expert_bias` float32) load back to the same
    logits. A synthetic state dict: no published checkpoint is at hand, and
    the loader says so."""
    from safetensors.numpy import save_file

    from dynamo_tpu.engine.jax_engine.weights import load_or_init_params

    cfg = dataclasses.replace(M.ConvMoeConfig.from_hf_dict(HF), attn_impl="xla")
    params = M.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    out: dict[str, np.ndarray] = {}

    def put(name, w, transpose=True):
        w = np.asarray(w, np.float32)
        out[name] = np.ascontiguousarray(w.T if transpose else w)

    for i, layer in enumerate(params["layers"]):
        p = f"model.layers.{i}."
        put(p + "operator_norm.weight", layer["op_norm"], False)
        put(p + "ffn_norm.weight", layer["ffn_norm"], False)
        if cfg.is_attn_layer(i):
            for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"), ("wo", "out_proj")):
                put(f"{p}self_attn.{theirs}.weight", layer[ours])
            put(p + "self_attn.q_layernorm.weight", layer["q_norm"], False)
            put(p + "self_attn.k_layernorm.weight", layer["k_norm"], False)
        else:
            put(p + "conv.in_proj.weight", layer["w_in"])
            put(p + "conv.out_proj.weight", layer["w_out"])
            out[p + "conv.conv.weight"] = np.ascontiguousarray(
                np.asarray(layer["conv_w"], np.float32).T[:, None, :]
            )
        f = p + "feed_forward."
        names = (("wg", "w1"), ("wu", "w3"), ("wd", "w2"))
        if cfg.is_moe_layer(i):
            put(f + "gate.weight", layer["router"])
            put(f + "expert_bias", layer["router_bias"], False)
            for e in range(cfg.num_experts):
                for ours, theirs in names:
                    put(f"{f}experts.{e}.{theirs}.weight", layer[ours][e])
        else:
            for ours, theirs in names:
                put(f"{f}{theirs}.weight", layer[ours])
    put("model.embed_tokens.weight", params["embed"], False)
    put("model.embedding_norm.weight", params["final_norm"], False)
    put("lm_head.weight", params["embed"], False)  # tied, written out again
    model_dir = write_model_dir(tmp_path)
    save_file(out, os.path.join(model_dir, "model.safetensors"))
    loaded = load_or_init_params(model_dir, cfg, dtype=jnp.float32)
    assert len(loaded["layers"]) == cfg.num_layers and "lm_head" not in loaded
    assert loaded["layers"][0]["conv_w"].shape == (3, 64)
    assert loaded["layers"][2]["router_bias"].dtype == jnp.float32
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    prompt = prompt_tokens(12, 9)
    head, last = pack([prompt], tables_for()[[0]], 16)
    slots = slots_of(LANES, [0])
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
        write_model_dir(tmp_path, dict(HF, torch_dtype="bfloat16")), name="t",
        kv_block_size=4, max_batch=4, **{"num_blocks": 96, **kw},
    )
    assert isinstance(engine.runner.config, M.ConvMoeConfig)
    return engine


async def test_served_through_the_engine_with_both_ledgers_and_no_block_hashes(tmp_path, monkeypatch):
    """`build_jax_engine` on an `lfm2_moe` directory: the same engine,
    programs and cache manager. Two prompts (one chunked beside the other's
    decoding, at an 8-token step budget) stream exactly their tokens, alike
    in two runs; the ledger's `ssm` slot counts what the lane arrays said of
    the tail slots and its `moe` slot what the device counted of the
    experts; three layer bodies a pass; no block hash is published; wiring
    disaggregation or a peer pull is refused in words."""
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
        ssm, moe = summary["ssm"], summary["moe"]
        assert ssm["slot_resets"] == 4 and ssm["scan_tokens"] == 2 * (9 + 30)
        assert ssm["layer_steps"] > 0 and ssm["layer_steps"] % 4 == 0
        assert 0 < ssm["slots_live"] <= 4 * ssm["layer_steps"] // 4
        # 4 expert layers a step; a live lane's token goes to 4 of 8 experts
        assert moe["layer_steps"] > 0 and moe["layer_steps"] % 4 == 0
        assert moe["assignments"] % 4 == 0
        assert 4 * moe["layer_steps"] <= moe["experts_touched"] <= 8 * moe["layer_steps"]
        labels = set(summary["compile_s_by_label"])
        assert labels <= {"prefill_packed", "prefill_chunk", "mixed_step@c1", "mixed_step@c2",
                          "decode", "decode_multi@H4B4"}, labels
        bodies = {k: v["layer_bodies"] for k, v in summary["first_dispatch_by_label"].items()}
        assert all(bodies[k] == (6 if k.startswith("mixed") else 3) for k in bodies), bodies
        assert stored == []
        for wire in ("remote_prefill_client", "peer_block_client"):
            with pytest.raises(ValueError, match="keep a recurrent state"):
                setattr(engine, wire, object())
            setattr(engine, wire, None)
    finally:
        await engine.close()


async def test_a_preempted_sequence_replays_to_the_same_greedy_tokens(tmp_path, monkeypatch):
    """A sequence is preempted in the middle of its answer (its slot and
    blocks freed), and its replay from position 0 (prompt and generated
    tokens through the prefill program, the tail zeroed there) streams the
    tokens an undisturbed run streams."""
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
    finally:
        await engine.close()


# ------------------------------------------- (i) run in=http out=jax


def test_run_http_jax_streams_exact_token_counts(tmp_path):
    """`python -m dynamo_tpu.run in=http out=jax` on the toy directory, no
    option, variable or model name beyond what every model gets: streamed
    completions of exactly the tokens asked for, and `/debug/goodput` with
    the `ssm` and the `moe` slots."""
    import http.client
    import signal
    import socket
    import subprocess
    import time

    model_dir = write_model_dir(tmp_path / "m", dict(HF, torch_dtype="bfloat16"))
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
        assert ledger["moe"]["layer_steps"] > 0 and ledger["moe"]["experts_touched"] > 0
        assert ledger["decode_tokens"] + 2 >= 5 + 17 - 2
    finally:
        proc.send_signal(signal.SIGINT)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()
        log.close()
