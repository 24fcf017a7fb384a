"""MoE ops + Mixtral family vs naive per-token oracles.

Mirrors the reference's strategy of testing routing logic hardware-free
(its WideEP path is only exercised through SGLang): the dropless dispatch
and the two expert-parallel paths on the CPU mesh must each equal a
per-token Python loop, and the full engine must generate identically with
experts sharded over ep.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.util import layer_caches
from dynamo_tpu.models import mixtral
from dynamo_tpu.ops.basics import swiglu
from dynamo_tpu.ops.moe import moe_ffn_shard_map, router_topk
from dynamo_tpu.parallel.mesh import build_mesh


def naive_moe(x, router_w, wg, wu, wd, top_k):
    """Per-token oracle: loop over tokens and their top-k experts."""
    T, D = x.shape
    logits = np.asarray(x, np.float32) @ np.asarray(router_w, np.float32)
    out = np.zeros((T, D), np.float32)
    for t in range(T):
        order = np.argsort(-logits[t])[:top_k]
        w = np.exp(logits[t][order] - logits[t][order].max())
        w = w / w.sum()
        for e, we in zip(order, w):
            h = np.asarray(x[t], np.float32)
            gate = h @ np.asarray(wg[e], np.float32)
            up = h @ np.asarray(wu[e], np.float32)
            act = np.asarray(
                swiglu(jnp.asarray(gate), jnp.asarray(up)), np.float32
            )
            out[t] += we * (act @ np.asarray(wd[e], np.float32))
    return out


def _weights(E, D, F, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (
        jax.random.normal(ks[0], (D, E)) / np.sqrt(D),
        jax.random.normal(ks[1], (E, D, F)) / np.sqrt(D),
        jax.random.normal(ks[2], (E, D, F)) / np.sqrt(D),
        jax.random.normal(ks[3], (E, F, D)) / np.sqrt(F),
    )


def test_router_topk_renormalizes():
    logits = jnp.array([[1.0, 3.0, 2.0, -1.0]])
    idx, w = router_topk(logits, 2)
    assert set(np.asarray(idx[0]).tolist()) == {1, 2}
    np.testing.assert_allclose(np.asarray(w).sum(), 1.0, rtol=1e-6)


@pytest.mark.slow
def test_moe_shard_map_matches_naive():
    mesh = build_mesh(ep=4)
    T, D, F, E = 12, 8, 16, 8
    x = jax.random.normal(jax.random.PRNGKey(10), (T, D))
    rw, wg, wu, wd = _weights(E, D, F, seed=1)
    ref = naive_moe(x, rw, wg, wu, wd, 2)
    out = moe_ffn_shard_map(
        mesh, x, rw, wg, wu, wd, top_k=2, capacity_factor=float(E)
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=1e-4, rtol=1e-4
    )


def test_mixtral_safetensors_roundtrip(tmp_path):
    """HF-format Mixtral tensors load into the MoE param tree."""
    import json

    from safetensors.numpy import save_file

    from dynamo_tpu.engine.jax_engine.weights import load_hf_safetensors

    cfg = mixtral.tiny_moe(num_experts=2)
    ref = mixtral.init_params(cfg, jax.random.PRNGKey(4), dtype=jnp.float32)
    def c(x):  # safetensors silently corrupts non-contiguous views
        return np.ascontiguousarray(np.asarray(x))

    tensors = {
        "model.embed_tokens.weight": c(ref["embed"]),
        "model.norm.weight": c(ref["final_norm"]),
        "lm_head.weight": c(np.asarray(ref["lm_head"]).T),
    }
    for i, lyr in enumerate(ref["layers"]):
        p = f"model.layers.{i}."
        tensors[p + "input_layernorm.weight"] = c(lyr["attn_norm"])
        tensors[p + "post_attention_layernorm.weight"] = c(lyr["mlp_norm"])
        for ours, hf in (("wq", "q_proj"), ("wk", "k_proj"),
                         ("wv", "v_proj"), ("wo", "o_proj")):
            tensors[p + f"self_attn.{hf}.weight"] = c(np.asarray(lyr[ours]).T)
        m = p + "block_sparse_moe."
        tensors[m + "gate.weight"] = c(np.asarray(lyr["router"]).T)
        for e in range(cfg.num_experts):
            tensors[f"{m}experts.{e}.w1.weight"] = c(np.asarray(lyr["wg"][e]).T)
            tensors[f"{m}experts.{e}.w3.weight"] = c(np.asarray(lyr["wu"][e]).T)
            tensors[f"{m}experts.{e}.w2.weight"] = c(np.asarray(lyr["wd"][e]).T)
    save_file(tensors, str(tmp_path / "model.safetensors"))
    json.dump({}, open(tmp_path / "config.json", "w"))

    loaded = load_hf_safetensors(str(tmp_path), cfg, dtype=jnp.float32)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6
        ),
        loaded,
        ref,
    )


@pytest.mark.slow
def test_mixtral_prefill_decode_runs():
    cfg = mixtral.tiny_moe()
    params = mixtral.init_params(cfg, jax.random.PRNGKey(0))
    bs, nb = 16, 8
    shape = (cfg.num_layers, cfg.num_kv_heads, nb, bs, cfg.head_dim)
    kc = layer_caches(shape, jnp.bfloat16)
    vc = layer_caches(shape, jnp.bfloat16)
    tokens = jnp.arange(16, dtype=jnp.int32) % cfg.vocab_size
    logits, kc, vc = mixtral.prefill(
        params, cfg, tokens, jnp.int32(16), kc, vc,
        jnp.array([1], jnp.int32),
    )
    assert logits.shape == (cfg.vocab_size,)
    toks = jnp.array([5, 9], jnp.int32)
    logits_d, kc, vc = mixtral.decode(
        params, cfg, toks, jnp.array([16, 3], jnp.int32), kc, vc,
        jnp.tile(jnp.arange(4, dtype=jnp.int32), (2, 1)),
        jnp.array([65, 66], jnp.int32),
    )
    assert logits_d.shape == (2, cfg.vocab_size)
    assert not bool(jnp.isnan(logits_d).any())


@pytest.mark.slow
def test_mixtral_engine_ep_mesh_matches_single_device():
    """Full engine generate with experts over ep=2 x tp=2 == single device."""
    import asyncio

    from dynamo_tpu.engine.jax_engine.engine import JaxEngine, JaxEngineConfig
    from dynamo_tpu.engine.jax_engine.model_runner import ModelRunner
    from dynamo_tpu.parallel.sharding import shard_llama
    from dynamo_tpu.pipeline.context import Context
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    cfg = mixtral.tiny_moe(num_experts=4)
    params = mixtral.init_params(cfg, jax.random.PRNGKey(2))

    def make(mesh, kv_sharding, p):
        runner = ModelRunner(
            cfg, p, num_blocks=64, block_size=16, max_batch=4,
            max_model_len=128, mesh=mesh, kv_sharding=kv_sharding,
        )
        return JaxEngine(
            runner,
            JaxEngineConfig(
                max_batch=4, block_size=16, num_blocks=64, max_model_len=128
            ),
        )

    mesh = build_mesh(ep=2, tp=2)
    ep_params, kv_sharding = shard_llama(mesh, cfg, params)

    async def run(engine):
        req = PreprocessedRequest(
            token_ids=list(range(2, 30)),
            sampling=SamplingOptions(greedy=True),
            stop=StopConditions(max_tokens=6, ignore_eos=True),
        )
        toks = []
        async for out in engine.generate(req, Context()):
            toks.extend(out.token_ids)
        return toks

    loop = asyncio.get_event_loop_policy().new_event_loop
    t_ep = loop().run_until_complete(run(make(mesh, kv_sharding, ep_params)))
    t_1 = loop().run_until_complete(run(make(None, None, params)))
    assert t_ep == t_1, (t_ep, t_1)


def test_moe_dropless_matches_naive():
    """Sort + ragged_dot grouped-GEMM dispatch: exact (dropless) semantics
    even under pathological routing imbalance (every token -> one expert)."""
    from dynamo_tpu.ops.moe import moe_ffn_dropless

    T, D, F, E = 96, 8, 16, 4
    rw, wg, wu, wd = _weights(E, D, F, seed=5)
    rw = jnp.zeros((D, E)).at[:, 1].set(5.0).at[:, 2].set(4.0)  # imbalance
    x = jax.random.normal(jax.random.PRNGKey(12), (T, D))
    out = moe_ffn_dropless(x, rw, wg, wu, wd, top_k=2)
    ref = naive_moe(x, rw, wg, wu, wd, 2)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4, rtol=1e-4)


def test_moe_ep_a2a_matches_naive():
    """Token-sharded all-to-all EP dispatch (DeepEP equivalent) == oracle."""
    from dynamo_tpu.ops.moe import moe_ffn_ep_a2a

    mesh = build_mesh(ep=4)
    T, D, F, E = 32, 8, 16, 8
    rw, wg, wu, wd = _weights(E, D, F, seed=8)
    x = jax.random.normal(jax.random.PRNGKey(15), (T, D))
    ref = naive_moe(x, rw, wg, wu, wd, 2)
    out = jax.jit(
        lambda x: moe_ffn_ep_a2a(
            mesh, x, rw, wg, wu, wd, top_k=2, capacity_factor=4.0
        )
    )(x)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4, rtol=1e-4)


def test_moe_ep_a2a_with_tp():
    """a2a dispatch with each expert's FFN additionally tp-sharded."""
    from dynamo_tpu.ops.moe import moe_ffn_ep_a2a

    mesh = build_mesh(ep=2, tp=2)
    T, D, F, E = 16, 8, 16, 4
    rw, wg, wu, wd = _weights(E, D, F, seed=9)
    x = jax.random.normal(jax.random.PRNGKey(16), (T, D))
    ref = naive_moe(x, rw, wg, wu, wd, 2)
    out = jax.jit(
        lambda x: moe_ffn_ep_a2a(
            mesh, x, rw, wg, wu, wd, top_k=2, capacity_factor=4.0,
            tp_axis="tp",
        )
    )(x)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("eps", [1e-20, 1e-6])
def test_sigmoid_router_bias_moves_the_choice_and_never_the_weights(eps):
    """`router_sigmoid_topk`: the bias takes part in the choice only; the
    weights are the chosen experts' own scores over (their sum + `eps`), 1e-20
    by default (DeepSeek-V3's, the programs JoyAI compiles) and 1e-6 where a
    family says so (LFM2's published routing)."""
    import inspect

    from dynamo_tpu.ops.moe import router_sigmoid_topk

    assert inspect.signature(router_sigmoid_topk).parameters["eps"].default == 1e-20
    logits = jnp.asarray([[2.0, 1.0, 0.5, -1.0, 0.0, -3.0]], jnp.float32)
    plain, w_plain = router_sigmoid_topk(logits, jnp.zeros(6), 2, eps=eps)
    assert sorted(np.asarray(plain[0]).tolist()) == [0, 1]
    # a bias that lifts the last expert over every other: it is chosen, and
    # its weight is its own small score's share, not the lifted one's
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.0, 0.0, 5.0], jnp.float32)
    idx, w = router_sigmoid_topk(logits, bias, 2, scale=1.0, eps=eps)
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 5]
    s = np.asarray(jax.nn.sigmoid(logits[0]), np.float64)
    want = {0: s[0] / (s[0] + s[5] + eps), 5: s[5] / (s[0] + s[5] + eps)}
    for e, got in zip(np.asarray(idx[0]).tolist(), np.asarray(w[0]).tolist()):
        assert abs(got - want[e]) < 1e-6, (e, got, want[e])
    # the weights of an unmoved choice do not see the bias at all
    small = jnp.asarray([0.01, 0.02, 0.0, 0.0, 0.0, 0.0], jnp.float32)
    idx2, w2 = router_sigmoid_topk(logits, small, 2, eps=eps)
    np.testing.assert_array_equal(np.asarray(idx2), np.asarray(plain))
    np.testing.assert_array_equal(np.asarray(w2), np.asarray(w_plain))
    # the normaliser is told apart where the scores are tiny
    tiny = jnp.full((1, 6), -20.0, jnp.float32)
    _, w_tiny = router_sigmoid_topk(tiny, jnp.zeros(6), 2, eps=eps)
    total = float(w_tiny.sum())
    assert (total > 0.99) if eps == 1e-20 else (total < 0.01)


# ----------------------------------------------- a held share of the experts


def _held_case(T=12, k=3, R=16, D=8, F=10, seed=5):
    """Assignments of T tokens over a router of R experts, a latent-width
    input, and all R experts' two-product stacks."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (T, D), jnp.float32)
    scores = jax.random.uniform(ks[1], (T, R))
    weights, idx = jax.lax.top_k(scores, k)
    wu = jax.random.normal(ks[2], (R, D, F)) / np.sqrt(D)
    wd = jax.random.normal(ks[3], (R, F, D)) / np.sqrt(F)
    return x, idx.astype(jnp.int32), weights, wu, wd


def _relu2_loop(x, idx, weights, wu, wd, first, held, valid=None):
    """Per-token oracle of the held part: expert e of the router is stack
    row e - first; an expert outside [first, first + held) adds nothing."""
    x, wu, wd = (np.asarray(a, np.float64) for a in (x, wu, wd))
    out = np.zeros(x.shape, np.float64)
    for t in range(x.shape[0]):
        if valid is not None and not bool(valid[t]):
            continue
        for e, w in zip(np.asarray(idx[t]).tolist(), np.asarray(weights[t]).tolist()):
            if first <= e < first + held:
                up = np.maximum(x[t] @ wu[e - first], 0.0)
                out[t] += w * ((up * up) @ wd[e - first])
    return out


@pytest.mark.parametrize("first,held", [(0, 16), (0, 4), (4, 4), (12, 4)])
def test_held_range_squared_relu_against_a_loop(first, held):
    """`dropless_experts(first_held=, form="relu2")`: the stacks hold the
    router's experts `[first, first + held)`; the result is their part of
    the sum, two products with a squared ReLU between them and no gate; an
    assignment outside the range, like a padding token's, reaches no group."""
    from dynamo_tpu.ops.moe import dropless_experts

    x, idx, weights, wu, wd = _held_case()
    valid = jnp.asarray([True] * 9 + [False] * 3)
    y, sizes = dropless_experts(
        x, idx, weights, None, wu[first: first + held], wd[first: first + held],
        valid, first_held=first, form="relu2",
    )
    want = _relu2_loop(x, idx, weights, wu[first: first + held], wd[first: first + held],
                       first, held, valid)
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-4, rtol=1e-4)
    live = np.asarray(idx)[:9].ravel()
    counts = np.bincount(live[(live >= first) & (live < first + held)] - first, minlength=held)
    np.testing.assert_array_equal(np.asarray(sizes), counts)
    assert np.all(np.asarray(y)[9:] == 0.0)


def test_no_row_is_computed_for_an_assignment_outside_the_held_range():
    """Held assignments sort in front and the rows behind the last group (the
    absent experts' and the padding's) are given to no expert and kept by
    nobody: a token none of whose experts is held gets exact zeros, each
    share's groups count its own assignments only, and the four shares of a
    router's 16 experts add up to what one stack of all 16 gives."""
    from dynamo_tpu.ops.moe import dropless_experts

    x, idx, weights, wu, wd = _held_case()
    whole, sizes_whole = dropless_experts(x, idx, weights, None, wu, wd, form="relu2")
    parts, held_sizes = [], []
    for first in (0, 4, 8, 12):
        y, sizes = dropless_experts(
            x, idx, weights, None, wu[first: first + 4], wd[first: first + 4],
            first_held=first, form="relu2",
        )
        parts.append(np.asarray(y))
        held_sizes.append(np.asarray(sizes))
    np.testing.assert_allclose(sum(parts), np.asarray(whole), atol=1e-4, rtol=1e-4)
    np.testing.assert_array_equal(np.concatenate(held_sizes), np.asarray(sizes_whole))
    # a share that holds nothing a token chose gives exact zeros for it
    none_held = np.all((np.asarray(idx) < 4) | (np.asarray(idx) >= 8), axis=1)
    assert np.all(parts[1][none_held] == 0.0)


def test_the_default_is_the_parents_program_to_the_bit():
    """With no held range and the SwiGLU form, `dropless_experts` traces to
    the jaxpr it traced to before it took either option (what JoyAI's and
    LFM2's programs compile), and `expert_step_stats` to its four numbers."""
    from dynamo_tpu.ops import moe

    router_w, wg, wu, wd = _weights(8, 16, 24)
    x = jax.random.normal(jax.random.PRNGKey(1), (10, 16))
    idx, w = router_topk(x @ router_w, 2)
    valid = jnp.arange(10) < 7

    def parents(x, idx, weights, wg, wu, wd, valid):
        T, D = x.shape
        k = idx.shape[1]
        E = wg.shape[0]
        e_flat = idx.reshape(-1).astype(jnp.int32)
        e_flat = jnp.where(jnp.repeat(valid, k), e_flat, E)
        order = jnp.argsort(e_flat)
        xs = x[order // k]
        group_sizes = jnp.sum(
            e_flat[:, None] == jnp.arange(E, dtype=jnp.int32)[None, :],
            axis=0, dtype=jnp.int32,
        )
        ys = moe._grouped_ffn(xs, group_sizes, wg, wu, wd)
        live = jnp.arange(T * k) < jnp.sum(group_sizes)
        ys = jnp.where(live[:, None], ys.astype(jnp.float32), 0.0)
        inv = jnp.zeros_like(order).at[order].set(jnp.arange(T * k, dtype=order.dtype))
        y = ys[inv].reshape(T, k, D) * weights.astype(jnp.float32)[:, :, None]
        return y.sum(axis=1), group_sizes

    args = (x, idx, w, wg, wu, wd, valid)
    assert str(jax.make_jaxpr(moe.dropless_experts)(*args)) == str(jax.make_jaxpr(parents)(*args))
    got, want = moe.dropless_experts(*args), parents(*args)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    sizes = jnp.asarray([3, 0, 2, 1], jnp.int32)
    assert moe.STEP_STATS == ("layer_steps", "assignments", "experts_touched", "max_expert_load")
    np.testing.assert_array_equal(np.asarray(moe.expert_step_stats(sizes)), [1, 6, 3, 3])


@pytest.mark.parametrize("live", [0, 5, 12])
def test_assignments_made_counts_every_assignment_of_the_live_tokens(live):
    """A held layer's fifth counter: `T x k` of the live tokens, the absent
    experts' assignments among them, beside the held ones in the second."""
    from dynamo_tpu.ops import moe

    x, idx, weights, wu, wd = _held_case()
    valid = jnp.arange(12) < live
    _, sizes = moe.dropless_experts(
        x, idx, weights, None, wu[4:8], wd[4:8], valid, first_held=4, form="relu2")
    made = jnp.sum(valid.astype(jnp.int32)) * idx.shape[1]
    counted = np.asarray(moe.expert_step_stats(sizes, made))
    assert moe.HELD_STEP_STATS[-1] == "assignments_made" and counted.shape == (5,)
    chosen = np.asarray(idx)[:live].ravel()
    assert counted[4] == live * 3
    assert counted[1] == np.sum((chosen >= 4) & (chosen < 8)) <= counted[4]
