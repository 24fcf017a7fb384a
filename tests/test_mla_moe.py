"""The latent-attention, sparse-expert family (`models/mla_moe.py`) at a toy
size of the same structure (a leading dense layer, a shared expert, a
correction bias, a scale, interleaved rope, a query bottleneck), held to the
plain float32 reference of `cellbench/reference/mla_moe.py` on logits; what
the factory refuses for it; its checkpoint names; and that the existing
configurations' programs are what they were.

Tolerances. In float32 the program and the reference compute the same
numbers in another order (absorbed against per-head attention, a grouped
product against a loop over experts): 2e-5 of the logits' spread is ten
times what such runs read (1e-6 to 2e-6) and a hundredth of the smallest
difference a wrong form makes (a missing bias, scale or rope pair reads
1e-2 and more). In bfloat16 the toy reads 0.017, the width of bfloat16's
mantissa through three layers; 0.05 holds it to the same order.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import importlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from cellbench.compare import logit_error  # noqa: E402
from cellbench.reference import mla_moe as R  # noqa: E402
from dynamo_tpu.engine.jax_engine.model_runner import ModelRunner  # noqa: E402
from dynamo_tpu.models import (  # noqa: E402
    cache_kind, config_from_model_dir, forward_for, layer_cache_kinds,
)
from dynamo_tpu.models import llama as L  # noqa: E402
from dynamo_tpu.models import mla_moe as M  # noqa: E402
from dynamo_tpu.ops import mla  # noqa: E402
from dynamo_tpu.ops.moe import router_sigmoid_topk  # noqa: E402
from dynamo_tpu.ops.sampling import MAX_EOS_IDS  # noqa: E402

HF = {
    "model_type": "joyai_llm_flash", "hidden_size": 64, "intermediate_size": 160,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4, "q_lora_rank": 48,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "n_routed_experts": 16, "num_experts_per_tok": 4,
    "n_shared_experts": 1, "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "vocab_size": 300, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "rope_interleave": True, "rope_scaling": None, "n_group": 1,
    "topk_group": 1, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "max_position_embeddings": 128, "tie_word_embeddings": False,
    "num_nextn_predict_layers": 1, "attention_bias": False,
}
BS, NB, MAX_BLOCKS = 4, 40, 8
F32_TOL, BF16_TOL = 2e-5, 0.05


@functools.lru_cache(maxsize=None)
def toy(attn_impl: str = "xla"):
    """(config, float32 params handed over from the reference's own draw,
    the reference's dims, layers and top)."""
    cfg = dataclasses.replace(M.MlaMoeConfig.from_hf_dict(HF), attn_impl=attn_impl)
    d = R.dims(HF)
    *layers, top = list(R.seeded_layers(d, 0))
    params = {
        "layers": [{k: v.astype(jnp.float32) for k, v in l.items()} for l in layers],
        "embed": top["embed"].astype(jnp.float32),
        "final_norm": top["final_norm"],
        "lm_head": top["lm_head"].astype(jnp.float32),
    }
    return cfg, params, d, layers, top


def planes(cfg, dtype=jnp.float32):
    kind = cfg.cache_kind()
    return tuple(
        jnp.zeros((1, NB, BS, kind.stored_width), dtype)
        for _ in range(cfg.num_layers)
    )


def prompt_tokens(n: int, seed: int) -> list[int]:
    return np.random.default_rng(seed).integers(3, HF["vocab_size"], n).tolist()


def pack(prompts: list[list[int]], tables: np.ndarray, P: int):
    """The arrays of one packed prefill of `prompts` into their tables."""
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
    return tuple(jnp.asarray(a) for a in (tokens, positions, segments, slots)), jnp.asarray(last, jnp.int32)


def greedy(B):
    """(keys, temps, top_ps, top_ks, want_lps): every lane greedy, every
    lane asking for its log-probs."""
    return (
        jnp.zeros((B, 2), jnp.uint32), jnp.zeros(B, jnp.float32),
        jnp.ones(B, jnp.float32), jnp.zeros(B, jnp.int32), jnp.ones(B, bool),
    )


def against_reference(d, layers, top, sequences, rows, top_ids, top_lps):
    """`cellbench/compare.py`'s number for served top log-probs of sequences
    of one length at `rows`."""
    want = np.asarray(R.forward(layers, top, d, sequences, rows))
    served, reference, stds = [], [], []
    for i in range(len(sequences)):
        for r in range(len(rows)):
            ids = np.asarray(top_ids[i][r], np.int64)
            served.append([float(x) for x in top_lps[i][r]])
            reference.append([float(x) for x in want[i, r, ids]])
            stds.append(float(np.std(want[i, r])))
    return logit_error(served, reference, stds)["rms_rel"]


# -------------------------------------------- (a) prefill, then decode_multi


@pytest.mark.parametrize("attn_impl,dtype,tol", [
    ("xla", "float32", F32_TOL), ("pallas_interpret", "float32", F32_TOL),
    ("xla", "bfloat16", BF16_TOL),
])
def test_packed_prefill_then_decode_multi_against_the_reference(attn_impl, dtype, tol):
    """Two prompts packed into one prefill (per-head form, rows written to
    the latent plane), then `decode_multi@H4` over the plane (absorbed
    form) with an idle third lane: the top-20 log-probs of every generated
    position against the reference's full forward."""
    cfg, params, d, layers, top = toy(attn_impl)
    dt = jnp.dtype(dtype)
    if dt != jnp.float32:
        params = jax.tree.map(lambda a: a.astype(dt), params)
        params["layers"] = [
            dict(l, router_bias=l["router_bias"].astype(jnp.float32))
            if "router_bias" in l else l for l in params["layers"]
        ]
    H, B, n = 4, 3, 11
    prompts = [prompt_tokens(n, 1), prompt_tokens(n, 2)]
    tables = np.zeros((B, MAX_BLOCKS), np.int32)
    tables[0], tables[1] = np.arange(1, 9), np.arange(9, 17)
    head, last = pack(prompts, tables, 32)
    logits, kc, _ = jax.jit(functools.partial(M.prefill_packed, params, cfg))(
        *head, planes(cfg, dt), (), last
    )
    first = np.asarray(jnp.argmax(logits, axis=-1), np.int32)  # [2]
    keys, temps, top_ps, top_ks, want = greedy(B)
    packed, kc, vc = jax.jit(
        functools.partial(ModelRunner._decode_multi_impl, cfg, None, None, BS),
        static_argnums=(0,),
    )(
        H, params, kc, (), jnp.asarray([first[0], first[1], 0], jnp.int32),
        jnp.asarray([n, n, 0], jnp.int32), jnp.asarray(tables), keys, temps,
        top_ps, top_ks, want, jnp.asarray([True, True, False]),
        jnp.full(B, 100, jnp.int32), jnp.zeros(B, jnp.int32),
        jnp.full((B, MAX_EOS_IDS), -1, jnp.int32),
    )
    packed = np.asarray(packed)
    assert vc == () and packed.shape[1] == B + 1  # the counters' row
    K = (packed.shape[-1] - 2) // 2
    toks = packed[:, :2, 0].astype(np.int64)  # [H, 2]
    assert (packed[:, 2, 0] == -1).all()  # the idle lane emits nothing
    sequences = [prompts[i] + [int(first[i])] + toks[:, i].tolist() for i in range(2)]
    rows = [n + h for h in range(H)]  # logits at row n + h predict toks[h]
    err = against_reference(
        d, layers, top, sequences, rows,
        [[packed[h, i, 2:2 + K] for h in range(H)] for i in range(2)],
        [[packed[h, i, 2 + K:] for h in range(H)] for i in range(2)],
    )
    assert err < tol, err
    # the prefill's own logits too (all ids, centred)
    want = np.asarray(R.forward(layers, top, d, [p for p in prompts], [n - 1]))[:, 0]
    got = np.asarray(logits, np.float32)
    rel = np.sqrt(np.mean(((got - got.mean(-1, keepdims=True)) - (want - want.mean(-1, keepdims=True))) ** 2)) / np.std(want)
    assert rel < tol, rel
    # the counters: two live lanes x 4 experts x 2 expert layers a step
    import types

    # (the row is the last one, also where the bucket is under --max-batch)
    counted = ModelRunner.step_stats(
        types.SimpleNamespace(config=cfg, max_batch=64), packed
    )
    assert counted["layer_steps"] == H * 2
    assert counted["assignments"] == H * 2 * 2 * cfg.num_experts_per_tok
    assert 0 < counted["experts_touched"] <= counted["assignments"]
    assert counted["max_expert_load"] >= counted["layer_steps"]


# ------------------------------------- (b) a chunked prompt in a mixed step


def test_mixed_step_with_a_chunked_prompt_against_the_reference():
    """A 13-token prompt enters as two 8-token chunks (the second's tail is
    padding), each in a mixed step on a batch of two decoding lanes: the
    chunk's first token and the lanes' tokens against the reference."""
    cfg, params, d, layers, top = toy("xla")
    B, n, C, n_long = 2, 9, 8, 13
    prompts = [prompt_tokens(n, 3), prompt_tokens(n, 4)]
    long_prompt = prompt_tokens(n_long, 5)
    tables = np.zeros((B, MAX_BLOCKS), np.int32)
    tables[0], tables[1] = np.arange(1, 9), np.arange(9, 17)
    long_table = np.arange(17, 25).astype(np.int32)
    head, last = pack(prompts, tables, 32)
    logits, kc, _ = jax.jit(functools.partial(M.prefill_packed, params, cfg))(
        *head, planes(cfg), (), last
    )
    tok = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
    keys, temps, top_ps, top_ks, want = greedy(B)
    mixed = jax.jit(functools.partial(ModelRunner._mixed_impl, cfg, None, None))
    sequences = [p + [int(t)] for p, t in zip(prompts, tok)]
    lane_ids, lane_lps, chunk_out = [[], []], [[], []], None
    for step, start in enumerate((0, C)):
        ctoks = np.zeros(C, np.int32)
        part = long_prompt[start:start + C]
        ctoks[:len(part)] = part
        chunk = (
            jnp.asarray(ctoks), jnp.int32(start), jnp.int32(n_long),
            jnp.asarray(long_table), jnp.zeros(2, jnp.uint32), jnp.float32(0.0),
            jnp.float32(1.0), jnp.int32(0), jnp.bool_(True), jnp.float32(1.0),
            jnp.full(MAX_EOS_IDS, -1, jnp.int32), jnp.bool_(False),
        )
        positions = np.asarray([n + step, n + step], np.int32)
        slots = tables[np.arange(B), positions // BS] * BS + positions % BS
        outs, kc, _ = mixed(
            params, kc, (), (chunk,), jnp.asarray(tok), jnp.asarray(positions),
            jnp.asarray(tables), jnp.asarray(slots), keys, temps, top_ps, top_ks, want,
            jnp.full((B, MAX_EOS_IDS), -1, jnp.int32), jnp.zeros(B, bool),
        )
        chunk_out, (tok, _, ids, lps) = outs[:4], outs[4:8]
        tok = np.asarray(tok, np.int32)
        for i in range(B):
            sequences[i].append(int(tok[i]))
            lane_ids[i].append(np.asarray(ids[i]))
            lane_lps[i].append(np.asarray(lps[i]))
    err = against_reference(
        d, layers, top, [s[:-1] for s in sequences], [n, n + 1], lane_ids, lane_lps
    )
    assert err < F32_TOL, err
    err = against_reference(
        d, layers, top, [long_prompt], [n_long - 1],
        [[np.asarray(chunk_out[2])]], [[np.asarray(chunk_out[3])]],
    )
    assert err < F32_TOL, err


# --------------------------------------------- (c) absorbed against per-head


def test_absorbed_attention_is_per_head_attention():
    """One prompt through `prefill_packed` (per head) and through
    `prefill_chunk` from position 0 (absorbed, over the plane): the same
    logits and the same rows in the plane. And the Pallas decode kernel in
    interpret mode against the XLA gather form."""
    cfg, params, *_ = toy("xla")
    n, P = 14, 16
    prompt = prompt_tokens(n, 6)
    table = np.arange(1, 9).astype(np.int32)
    head, last = pack([prompt], table[None, :], P)
    per_head, kc_a, _ = M.prefill_packed(params, cfg, *head, planes(cfg), (), last)
    toks = np.zeros(P, np.int32)
    toks[:n] = prompt
    absorbed, kc_b, _ = M.prefill_chunk(
        params, cfg, jnp.asarray(toks), jnp.int32(0), jnp.int32(n), planes(cfg),
        (), jnp.asarray(table),
    )
    np.testing.assert_allclose(
        np.asarray(absorbed), np.asarray(per_head[0]), rtol=0, atol=2e-5
    )
    for a, b in zip(kc_a, kc_b):  # but the null block 0, where padding goes
        np.testing.assert_allclose(
            np.asarray(a)[:, 1:], np.asarray(b)[:, 1:], rtol=0, atol=2e-5
        )
    kind = cfg.cache_kind()
    assert (np.asarray(kc_a[0])[..., kind.width:] == 0).all()
    assert np.any(np.asarray(kc_a[0])[0, 1:4, :, : kind.width])
    rng = np.random.default_rng(0)
    B, Hq, W = 3, cfg.num_heads, kind.stored_width
    q = jnp.asarray(rng.normal(size=(B, Hq, W)), jnp.float32)
    plane = jnp.asarray(rng.normal(size=(1, NB, BS, W)), jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, 25)).reshape(B, 8), jnp.int32)
    ctx = jnp.asarray([29, 0, 5], jnp.int32)  # the second lane is idle
    outs = [
        np.asarray(mla.decode_attention(
            q, plane, tables, ctx, value_width=cfg.kv_lora_rank,
            scale=cfg.attn_scale, impl=impl,
        )) for impl in ("xla", "pallas_interpret")
    ]
    np.testing.assert_allclose(outs[0], outs[1], rtol=0, atol=2e-5)
    assert (outs[0][1] == 0).all() and (outs[1][1] == 0).all()


# ------------------------------------------------------- (d) the router alone


def test_router_selects_by_score_plus_bias_and_weighs_by_score():
    cfg, params, d, layers, _ = toy("xla")
    # the seeded bias is small (it leaves the loads to the scores); ten times
    # it changes the selection of most tokens among 16 experts
    layer = dict(layers[1], router_bias=10.0 * layers[1]["router_bias"])
    h = jnp.asarray(np.random.default_rng(7).normal(size=(64, d["hidden"])), jnp.float32)
    logits = jnp.matmul(h, layer["router"].astype(jnp.float32), precision="highest")
    idx, w = router_sigmoid_topk(
        logits, layer["router_bias"], d["top_k"], scale=d["route_scale"]
    )
    with jax.default_matmul_precision("highest"):
        want_idx, want_w = R.route(h, layer, d)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    np.testing.assert_allclose(np.asarray(w), np.asarray(want_w), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), d["route_scale"], rtol=1e-5)
    # the bias changes the selection, and the weights are the scores': a
    # router that selected by score alone, or weighed by score + bias, differs
    plain_idx, _ = router_sigmoid_topk(
        logits, jnp.zeros_like(layer["router_bias"]), d["top_k"], scale=d["route_scale"]
    )
    changed = np.any(np.sort(np.asarray(idx)) != np.sort(np.asarray(plain_idx)), axis=-1)
    assert changed.mean() > 0.2, changed.mean()
    s = np.asarray(jax.nn.sigmoid(logits))
    picked = np.take_along_axis(s, np.asarray(idx), axis=-1)
    np.testing.assert_allclose(
        np.asarray(w), d["route_scale"] * picked / picked.sum(-1, keepdims=True), rtol=1e-5
    )


def test_a_padding_token_is_given_to_no_expert():
    from dynamo_tpu.ops.moe import dropless_experts

    cfg, params, *_ = toy("xla")
    layer = params["layers"][1]
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=(6, cfg.hidden_size)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 16, (6, 4)), jnp.int32)
    w = jnp.asarray(rng.random((6, 4)), jnp.float32)
    valid = jnp.asarray([True, False, True, True, False, True])
    y, sizes = dropless_experts(x, idx, w, layer["wg"], layer["wu"], layer["wd"], valid)
    y_all, sizes_all = dropless_experts(x, idx, w, layer["wg"], layer["wu"], layer["wd"])
    assert int(sizes.sum()) == 4 * 4 and int(sizes_all.sum()) == 6 * 4
    np.testing.assert_allclose(np.asarray(y)[np.asarray(valid)], np.asarray(y_all)[np.asarray(valid)], rtol=1e-5, atol=1e-6)
    assert (np.asarray(y)[~np.asarray(valid)] == 0).all()


# ------------------------- (e) the published widths, by arithmetic, no arrays


def published():
    with open(os.path.join(REPO, "cellbench", "configs", "joyai-flash-bf16-l5.json")) as f:
        conf = json.load(f)
    return M.MlaMoeConfig.from_hf_dict({k: v for k, v in conf.items() if k != "bench"})


def test_param_count_and_block_budget_at_the_published_widths(monkeypatch):
    from dynamo_tpu.engine.jax_engine import factory

    cfg = published()
    attn = 3_145_728 + 9_437_184 + 1_179_648 + 4_194_304 + 8_388_608
    expert_layer = 256 * 4_718_592 + 4_718_592 + 524_288
    matrices = 5 * attn + 44_040_192 + 4 * expert_layer + 529_530_880
    assert attn == 26_345_472 and expert_layer == 1_213_202_432
    assert matrices == 5_558_108_160  # ISSUE 28's arithmetic: 11.12 GB in bf16
    vectors = 5 * (2 * 2048 + 1536 + 512) + 4 * 256 + 2048
    assert M.param_count(cfg) == matrices + vectors
    assert M.expert_param_count(cfg) == 4 * 256 * 4_718_592
    kind = cache_kind(cfg)
    assert (kind.name, kind.planes, kind.heads, kind.width) == ("latent", 1, 1, 576)
    # the plane's rows are stored 640 wide (a DMA tile is 128 lanes):
    # 5 x 640 x 2 = 6,400 bytes a token, not the 5,760 of 576 values
    assert kind.stored_width == 640
    monkeypatch.setenv("DYN_HBM_GB", str(16_909_336_064 / 2**30))
    blocks = factory.default_num_blocks(cfg, 8192, 64)
    want = 64 * 512 + 64
    fit = (int(16_909_336_064 * 0.85) - 2 * (matrices + vectors)) // (5 * 16 * 640 * 2)
    assert want == 32_832 and blocks == min(want, fit) == fit
    assert 31_000 < blocks < want  # 3.25 GB of planes beside 11.12 GB of weights
    # and a grouped-query model reckons as before
    dense = L.LlamaConfig(num_layers=32, num_kv_heads=8, head_dim=128)
    assert cache_kind(dense).stored_values_per_token() == 2 * 8 * 128
    assert factory.default_num_blocks(dense, 4096, 64, quantized=True) == min(
        64 * 256 + 64,
        (int(16_909_336_064 * 0.85) - L.param_count(dense)) // (2 * 32 * 16 * 8 * 128 * 2),
    )


# ----------------------------- (f) the existing configurations' programs


def cache_shapes(cfg, lanes: int):
    """The runner's two containers from what the config's layers declare
    they keep (`layer_cache_kinds`), as shapes: pages for a paged layer, the
    slot's arrays with a row a lane and the null lane's for a recurrent one,
    None where a layer keeps nothing there; and whether any layer keeps a
    slot (the programs then take each sequence's lane slot)."""
    sds = jax.ShapeDtypeStruct
    kinds = layer_cache_kinds(cfg)

    def held(kind, which):
        if kind.planes:
            return sds((kind.heads, NB, BS, kind.stored_width), jnp.bfloat16)
        if which >= len(kind.slot):
            return None
        shape, dtype = kind.slot[which]
        return sds((lanes + 1,) + tuple(shape), jnp.dtype(dtype))

    k = tuple(held(kind, 0) for kind in kinds)
    v = tuple(held(kind, 1) for kind in kinds) if cache_kind(cfg).planes == 2 else ()
    return k, v, any(kind.slot for kind in kinds)


def lowered(cfg, program: str) -> str:
    """StableHLO text of one step program at toy widths, lowered on the CPU
    from shapes alone."""
    B, H, C, P = 4, 4, 8, 16
    sds = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: forward_for(cfg).init_params(
        cfg, jax.random.PRNGKey(0), quantize=isinstance(cfg, L.LlamaConfig)
    ))
    k_cache, v_cache, slotted = cache_shapes(cfg, B)
    i32, f32 = jnp.int32, jnp.float32
    # where a layer keeps a slot a lane, a program that prefills is told each
    # sequence's, behind its other arguments
    slot = lambda *shape: (sds(shape, i32),) if slotted else ()
    lanes = (
        sds((B, 2), jnp.uint32), sds((B,), f32), sds((B,), f32), sds((B,), i32),
        sds((B,), jnp.bool_),
    )
    if program == "decode_multi":
        return jax.jit(
            functools.partial(ModelRunner._decode_multi_impl, cfg, None, None, BS),
            static_argnums=(0,),
        ).lower(
            H, params, k_cache, v_cache, sds((B,), i32), sds((B,), i32),
            sds((B, MAX_BLOCKS), i32), *lanes, sds((B,), jnp.bool_),
            sds((B,), i32), sds((B,), i32), sds((B, MAX_EOS_IDS), i32),
        ).as_text()
    if program == "mixed_step":
        chunk = (
            sds((C,), i32), sds((), i32), sds((), i32), sds((MAX_BLOCKS,), i32),
            sds((2,), jnp.uint32), sds((), f32), sds((), f32), sds((), i32),
            sds((), jnp.bool_), sds((), f32), sds((MAX_EOS_IDS,), i32),
            sds((), jnp.bool_), *slot(),
        )
        return jax.jit(
            functools.partial(ModelRunner._mixed_impl, cfg, None, None)
        ).lower(
            params, k_cache, v_cache, (chunk,), sds((B,), i32), sds((B,), i32),
            sds((B, MAX_BLOCKS), i32), sds((B,), i32), *lanes,
            sds((B, MAX_EOS_IDS), i32), sds((B,), jnp.bool_),
        ).as_text()
    if program == "prefill":
        return jax.jit(
            functools.partial(ModelRunner._prefill_impl, cfg, None, None)
        ).lower(
            params, k_cache, v_cache, sds((P,), i32), sds((), i32),
            sds((MAX_BLOCKS,), i32), sds((2,), jnp.uint32), sds((), f32),
            sds((), f32), sds((), i32), sds((), jnp.bool_), sds((), f32),
            sds((MAX_EOS_IDS,), i32), sds((), jnp.bool_), *slot(),
        ).as_text()
    return jax.jit(
        functools.partial(ModelRunner._prefill_packed_impl, cfg, None)
    ).lower(
        params, k_cache, v_cache, sds((P,), i32), sds((P,), i32), sds((P,), i32),
        sds((P,), i32), sds((2,), i32), sds((2, 2), jnp.uint32), sds((2,), f32),
        sds((2,), f32), sds((2,), i32), sds((2,), jnp.bool_), sds((2,), f32),
        sds((2, MAX_EOS_IDS), i32), sds((2,), jnp.bool_), *slot(2),
    ).as_text()


def operations(text: str) -> dict[str, int]:
    ops: dict[str, int] = {}
    for name in re.findall(r"= \"?((?:stablehlo|func|chlo)\.[a-z_]+)", text):
        ops[name] = ops.get(name, 0) + 1
    return dict(sorted(ops.items()))


# Read by this same code: the number of operations of each program, and the
# digest of its text, as PR 37 made them: a layer's body is one private
# function (`models.layer_body`) that the program calls `num_layers` times a
# pass, so a text holds one body for each kind of pass (one in `decode_multi`
# and `prefill_packed`, a chunk's and a decode's in `mixed_step`) beside the
# embedding, the head and the sampler, which stay in line once a step. As PR
# 32 left them, every layer in line: 3436/3396, 2199/2181 and 1081/1069
# operations; `decode_multi`, four passes over two layers, now holds fewer
# than `mixed_step`, two passes. A change to the grouped-query block's
# programs or to the sampler moves them; say so in PERF.md and re-read.
# Re-read in PR 47 for the two programs that decode: in the XLA form a decode
# layer goes through `ops.attention.decode_append_attention` to the same
# scatter and the same gather, so the counts are PR 37's, and the lanes'
# `live` compare now stands before the scatter, not behind it, so the texts'
# digests are new (04f787b092f94f4f, 4b44078fd9ec224b, ec18c988b51032e1,
# 74e83189d87bd651 before).
# Re-read in PR 53 for all twenty-two: every program's sampler takes the
# lanes' `want_logprobs` and holds the log-prob surface under a second
# `cond`, twelve operations a sampler call more (thirteen where the flag is a
# scalar widened to a lane: a prefill's and a chunk's tail); nothing else of
# a text moved. PR 50's: 1581/36b61c79679131e3, 1687, 839, 1576, 1678, 833,
# 2052, 2664, 1319, 1239, 1666, 2136, 1235, 1155, 2173, 2805, 1498, 1446,
# 1878, 2437, 1553, 1470 in the order below.
# Re-read in PR 55 for `ssm2_moe`'s two programs that decode, and no other
# entry: the Mamba-2 decode body's update goes through
# `ops.pallas_ssm.ssd_update`, which, where the plain form runs (here), pads
# the live mask to the slot rows itself beside the body's own pad for the
# tail: one `pad` a body more (1926/03fbeb4d4d974f7f, 2462/c9bfcce8917eafbf
# before), the same `ssd_step` on the same operands behind it.
PARENT_PROGRAMS = {
    ("mistral", "decode_multi"): (1629, "76e6d0321d223665"),
    ("mistral", "mixed_step"): (1712, "759ce9a772993302"),
    ("mistral", "prefill_packed"): (851, "18fb0ec552df6d17"),
    ("qwen", "decode_multi"): (1624, "f9f71943f858df2e"),
    ("qwen", "mixed_step"): (1703, "197c502ee8d9db16"),
    ("qwen", "prefill_packed"): (845, "aeaaf274988453f1"),
    # The four newer families at their test files' toy configs, read at
    # commit cc32b34 (PR 49) before PR 50 wrote their four step programs once
    # (`models/programs.py`): the text a family's own copy of the programs
    # lowered. `prefill` beside the three, because one family gives the whole
    # prompt bodies of its own. A change to a family's layer bodies, to the
    # shared programs or to the sampler moves them; say so and re-read.
    ("latent", "decode_multi"): (2100, "5a4e6fd2ade23217"),
    ("latent", "mixed_step"): (2689, "76952028362eca78"),
    ("latent", "prefill"): (1332, "501d4c0627cd4e03"),
    ("latent", "prefill_packed"): (1251, "5cb0ce2a93d8e4ff"),
    ("hybrid_ssm", "decode_multi"): (1714, "a9d1d341cc2a4e45"),
    ("hybrid_ssm", "mixed_step"): (2161, "fe083eda98778878"),
    ("hybrid_ssm", "prefill"): (1248, "53ac6a1a2d62bc79"),
    ("hybrid_ssm", "prefill_packed"): (1167, "3ee57776a3bbeafa"),
    ("conv_moe", "decode_multi"): (2221, "6c012ca30994a869"),
    ("conv_moe", "mixed_step"): (2830, "be5e60bf6a9c64ed"),
    ("conv_moe", "prefill"): (1511, "d7e43a1e5d5e107e"),
    ("conv_moe", "prefill_packed"): (1458, "626867b07f50d96b"),
    ("ssm2_moe", "decode_multi"): (1927, "f498cd337f20eb50"),
    ("ssm2_moe", "mixed_step"): (2463, "728220f1f353c081"),
    ("ssm2_moe", "prefill"): (1566, "54543ed180567f5b"),
    ("ssm2_moe", "prefill_packed"): (1482, "ec03fa7e84fd6a49"),
}


def program_config(family: str):
    """The benchmark's two dense configurations' structure at toy widths, or
    a newer family's toy config as its own test file declares it."""
    if family == "mistral":
        return L.LlamaConfig(
            vocab_size=320, hidden_size=64, intermediate_size=160, num_layers=2,
            num_heads=8, num_kv_heads=2, head_dim=8, rope_theta=10000.0,
            sliding_window=4096, max_position_embeddings=64, attn_impl="xla",
        )
    if family == "qwen":
        return L.LlamaConfig(
            vocab_size=400, hidden_size=56, intermediate_size=144, num_layers=2,
            num_heads=7, num_kv_heads=1, head_dim=8, rope_theta=1e6, rms_eps=1e-6,
            attn_bias=True, max_position_embeddings=64, attn_impl="xla",
        )
    if family == "latent":
        return toy()[0]
    return importlib.import_module(f"tests.test_{family}").toy()[0]


@pytest.mark.parametrize("family,program", sorted(PARENT_PROGRAMS))
def test_existing_programs_lower_to_what_the_parent_lowered(family, program):
    text = lowered(program_config(family), program)
    count, digest = PARENT_PROGRAMS[(family, program)]
    assert sum(operations(text).values()) == count, operations(text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


# ------------------------------------------------------------ the satellites


def write_model_dir(path, hf=HF) -> str:
    from tests.util import make_test_tokenizer

    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(hf, f)
    make_test_tokenizer()._hf.save(os.path.join(path, "tokenizer.json"))
    return str(path)


def test_the_family_is_chosen_by_model_type(tmp_path):
    cfg = config_from_model_dir(write_model_dir(tmp_path / "a"))
    assert isinstance(cfg, M.MlaMoeConfig) and cfg.first_k_dense == 1
    dense = dict(HF, model_type="mistral", num_key_value_heads=2)
    assert isinstance(config_from_model_dir(write_model_dir(tmp_path / "b", dense)), L.LlamaConfig)
    with pytest.raises(ValueError, match="n_group.*not implemented"):
        M.MlaMoeConfig.from_hf_dict(dict(HF, n_group=8))
    with pytest.raises(ValueError, match="rope_scaling"):
        M.MlaMoeConfig.from_hf_dict(dict(HF, rope_scaling={"type": "yarn"}))


@pytest.mark.parametrize("asked,words", [
    (dict(kv_dtype="int8"), "int8-resident cache"),
    (dict(quantize=True), "int8 weights"),
    (dict(meshed=True), "mesh"),
    (dict(fused_decode=True), "fused decode"),
    (dict(env={"DYN_KV_HOST_OFFLOAD_GB": "1"}), "block-manager tiers"),
    (dict(env={"DYN_SPEC_K": "3"}), "speculative"),
])
def test_what_a_latent_cache_does_not_support_is_refused_in_words(monkeypatch, asked, words):
    from dynamo_tpu.engine.jax_engine.factory import refuse_unsupported

    asked = dict(asked)
    for k, v in asked.pop("env", {}).items():
        monkeypatch.setenv(k, v)
    cfg, *_ = toy("xla")
    with pytest.raises(ValueError, match=words):
        refuse_unsupported(cfg, **asked)
    refuse_unsupported(L.LlamaConfig.tiny(), **asked)  # grouped-query: untouched
    monkeypatch.undo()
    refuse_unsupported(cfg)  # and nothing asked, nothing refused


@pytest.mark.parametrize("how", ["tp", "ep"])
async def test_the_factory_refuses_a_mesh_before_it_builds_anything(tmp_path, how):
    from dynamo_tpu.engine.jax_engine.factory import build_jax_engine

    kw = {"tensor_parallel_size": 2} if how == "tp" else {"expert_parallel_size": 2}
    with pytest.raises(ValueError, match="mesh"):
        await build_jax_engine(
            write_model_dir(tmp_path), kv_block_size=4, max_batch=2, num_blocks=16, **kw
        )


def test_the_runner_refuses_int8_pages_a_mesh_and_block_transfer():
    cfg, params, *_ = toy("xla")
    kw = dict(num_blocks=NB, block_size=BS, max_batch=2, max_model_len=32, attn_impl="xla")
    with pytest.raises(ValueError, match="int8-resident"):
        ModelRunner(cfg, params, kv_dtype="int8", **kw)
    runner = ModelRunner(cfg, params, kv_dtype=jnp.float32, **kw)
    assert runner.v_cache == () and len(runner.k_cache) == cfg.num_layers
    assert runner.k_cache[0].shape == (1, NB, BS, cfg.cache_kind().stored_width)
    for call in (
        lambda: runner.extract_blocks([1, 2]),
        lambda: runner.extract_blocks_tight([1]),
        lambda: runner.extract_blocks_device([1]),
        lambda: runner.inject_blocks([1], None, None),
        lambda: runner.inject_blocks_device([1], None, None),
    ):
        with pytest.raises(ValueError, match="latent plane of .* values a token"):
            call()


async def test_served_through_the_engine_and_refused_for_disaggregation(tmp_path):
    """`build_jax_engine` on a `joyai_llm_flash` directory: the same engine,
    programs and cache manager; two prompts (one long enough to be chunked
    beside the other's decoding) stream their tokens, alike in two runs; the
    ledger holds the experts' counters; wiring a remote-prefill client, a
    peer pull or tiers is refused in words."""
    from dynamo_tpu.engine.jax_engine.factory import build_jax_engine
    from tests.test_colocated_disagg import collect_tokens
    import asyncio

    engine, _ = await build_jax_engine(
        write_model_dir(tmp_path), name="t", kv_block_size=4, max_batch=4,
        num_blocks=96,
    )
    try:
        assert isinstance(engine.runner.config, M.MlaMoeConfig)
        short, long = list(range(3, 12)), [3 + (7 * i) % 40 for i in range(70)]
        first = await asyncio.gather(
            collect_tokens(engine, short, 12), collect_tokens(engine, long, 6)
        )
        again = await asyncio.gather(
            collect_tokens(engine, short, 12), collect_tokens(engine, long, 6)
        )
        assert first == again and [len(t) for t in first] == [12, 6]
        moe = engine.stats.goodput.summary()["moe"]
        if engine.config.decode_horizon > 1:
            assert moe["layer_steps"] > 0 and moe["assignments"] > 0
            assert moe["experts_touched"] <= moe["assignments"]
        for wire in ("remote_prefill_client", "peer_block_client"):
            with pytest.raises(ValueError, match="latent plane"):
                setattr(engine, wire, object())
            setattr(engine, wire, None)
    finally:
        await engine.close()


def test_checkpoint_names_round_trip_to_the_seeded_logits(tmp_path):
    """The seeded weights written under the family's checkpoint names (and a
    multi-token-prediction layer behind them, which must be ignored) load
    back to the same logits."""
    from safetensors.numpy import save_file

    from dynamo_tpu.engine.jax_engine.weights import load_or_init_params

    cfg = M.MlaMoeConfig.from_hf_dict(HF)
    params = M.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    out: dict[str, np.ndarray] = {}

    def put(name, w, transpose=True):
        w = np.asarray(w, np.float32)
        out[name] = np.ascontiguousarray(w.T if transpose else w)

    names = {
        "wq_a": "self_attn.q_a_proj", "wq_b": "self_attn.q_b_proj",
        "wkv_a": "self_attn.kv_a_proj_with_mqa", "wkv_b": "self_attn.kv_b_proj",
        "wo": "self_attn.o_proj",
    }
    for i, layer in enumerate(params["layers"] + [params["layers"][-1]]):
        p = f"model.layers.{i}."
        put(p + "input_layernorm.weight", layer["attn_norm"], False)
        put(p + "post_attention_layernorm.weight", layer["mlp_norm"], False)
        put(p + "self_attn.q_a_layernorm.weight", layer["q_norm"], False)
        put(p + "self_attn.kv_a_layernorm.weight", layer["kv_norm"], False)
        for ours, theirs in names.items():
            put(p + theirs + ".weight", layer[ours])
        if "router" in layer:
            put(p + "mlp.gate.weight", layer["router"])
            put(p + "mlp.gate.e_score_correction_bias", layer["router_bias"], False)
            for e in range(cfg.n_routed_experts):
                for ours, theirs in (("wg", "gate"), ("wu", "up"), ("wd", "down")):
                    put(f"{p}mlp.experts.{e}.{theirs}_proj.weight", layer[ours][e])
            for ours, theirs in (("sg", "gate"), ("su", "up"), ("sd", "down")):
                put(f"{p}mlp.shared_experts.{theirs}_proj.weight", layer[ours])
        else:
            for ours, theirs in (("wg", "gate"), ("wu", "up"), ("wd", "down")):
                put(f"{p}mlp.{theirs}_proj.weight", layer[ours])
    put("model.embed_tokens.weight", params["embed"], False)
    put("model.norm.weight", params["final_norm"], False)
    put("lm_head.weight", params["lm_head"])
    model_dir = write_model_dir(tmp_path)
    save_file(out, os.path.join(model_dir, "model.safetensors"))
    loaded = load_or_init_params(model_dir, cfg, dtype=jnp.float32)
    assert len(loaded["layers"]) == cfg.num_layers
    prompt = prompt_tokens(12, 9)
    head, last = pack([prompt], np.arange(1, 9)[None, :], 16)
    a, *_ = M.prefill_packed(params, cfg, *head, planes(cfg), (), last)
    b, *_ = M.prefill_packed(loaded, cfg, *head, planes(cfg), (), last)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="int8 weights"):
        load_or_init_params(model_dir, cfg, quantize=True)


def test_the_chip_check_of_the_latent_kernel_runs_in_rehearsal(capsys):
    """`chip_mla_check.py` (the kernel and the path around it against the
    XLA form and the reference, for the chip) at toy widths with the
    interpreted kernel: it ends `ok` under its own limits."""
    import chip_mla_check

    assert chip_mla_check.main(["--cpu-rehearsal"]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["ok"] is True and last["device"]["platform"] == "cpu"

