"""Meshed fused decode parity (ISSUE 19): the fused decode-step kernels
under shard_map over the tp axis (`ops/collective.py`) vs the unfused
GSPMD-sharded op chain, plus the decomposed collective-matmul tail.

Parity bars (empirically calibrated, same policy as test_fused_decode):

  * per-op (fused_qkv_rope_meshed / fused_attn_out_residual_meshed vs the
    unfused ops on replicated params) is BIT-EXACT — the per-shard fused
    programs replay the unfused op/dtype sequence and the plain path
    psums in f32 exactly where GSPMD places the o-proj all-reduce;
  * whole-program (jitted llama.decode under a mesh) is token-exact and
    allclose on logits — inside one jit XLA may re-fuse the UNFUSED
    side's bf16 casts, so bitwise equality is not the contract there;
  * the overlap tail (DYN_COLLECTIVE_OVERLAP) reorders the f32 ring adds,
    so it holds the same token-exact + allclose bar vs the plain path.

Also covered: the fused-dispatch gate under tp=2 / tp=4 / dp x tp meshes
(kernel-entry counted via ops.linear.FUSED_KERNEL_ENTRIES), int8 weights
x int8 KV through a meshed ModelRunner, and the factory's int8-KV
block-size retune.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.util import layer_caches
from dynamo_tpu.models import llama as L
from dynamo_tpu.ops import linear as lin
from dynamo_tpu.ops.basics import rope_freqs
from dynamo_tpu.ops.collective import (
    fused_attn_out_residual_meshed,
    fused_qkv_rope_meshed,
)
from dynamo_tpu.ops.layers import attn_out, qkv_head
from dynamo_tpu.parallel.mesh import build_mesh
from dynamo_tpu.parallel.sharding import shard_llama

multichip = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs >=4 devices"
)


def _cfg(**kw):
    return dataclasses.replace(L.LlamaConfig.tiny(), **kw)


# ------------------------------------------------------------ per-op parity


@multichip
@pytest.mark.parametrize("quant", [False, True])
def test_meshed_fused_qkv_rope_bit_identical(quant):
    """Column-parallel QKV under shard_map: each shard runs the fused
    program on its head slice; outputs match the unfused replicated chain
    bit-for-bit."""
    cfg = _cfg()
    params = L.init_params(cfg, jax.random.PRNGKey(1), quantize=quant)
    mesh = build_mesh(tp=2, dp=1)
    sharded, _ = shard_llama(mesh, cfg, params)
    layer, slayer = params["layers"][0], sharded["layers"][0]
    B = 3
    x = jnp.asarray(
        np.random.default_rng(2).normal(size=(B, cfg.hidden_size)),
        jnp.bfloat16,
    )
    positions = jnp.asarray([7, 0, 31], jnp.int32)
    inv = rope_freqs(cfg.head_dim, cfg.rope_theta, None)
    q0, k0, v0 = qkv_head(x, layer, cfg, inv, positions)
    angles = positions[..., None].astype(jnp.float32) * inv
    q1, k1, v1 = fused_qkv_rope_meshed(
        mesh, x, slayer["attn_norm"],
        slayer["wq"], slayer["wk"], slayer["wv"],
        jnp.cos(angles), jnp.sin(angles),
        eps=cfg.rms_eps, num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        interpret=True,
    )
    for a, b in ((q0, q1), (k0, k1), (v0, v1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@multichip
@pytest.mark.parametrize("quant", [False, True])
def test_meshed_fused_attn_out_bit_identical(quant):
    """Row-parallel o-proj under shard_map: per-shard fused partials,
    f32 psum, then scale/cast/residual — bit-identical to the unfused
    replicated chain."""
    cfg = _cfg()
    params = L.init_params(cfg, jax.random.PRNGKey(3), quantize=quant)
    mesh = build_mesh(tp=2, dp=1)
    sharded, _ = shard_llama(mesh, cfg, params)
    layer, slayer = params["layers"][0], sharded["layers"][0]
    B = 3
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(B, cfg.hidden_size)), jnp.bfloat16)
    attn = jnp.asarray(
        rng.normal(size=(B, cfg.num_heads, cfg.head_dim)), jnp.bfloat16
    )
    o0 = attn_out(attn, x, layer, cfg)
    o1 = fused_attn_out_residual_meshed(
        mesh, attn.reshape(B, cfg.q_dim), slayer["wo"], x, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(o0), np.asarray(o1))


# -------------------------------------------------- whole-program parity


def _mesh_decode_once(cfg, params, mesh, *, fused, overlap=False):
    """One jitted llama.decode step (the serving program shape) under
    `mesh` (None = single-device); returns the logits."""
    c = dataclasses.replace(
        cfg, fused_decode=fused, collective_overlap=overlap
    )
    B, bs, nb = 3, 8, 32
    shape = (c.num_layers, c.num_kv_heads, nb, bs, c.head_dim)
    kc = layer_caches(shape, jnp.bfloat16)
    vc = layer_caches(shape, jnp.bfloat16)
    run_params = params
    if mesh is not None:
        run_params, kv_sharding = shard_llama(mesh, c, params)
        kc = jax.device_put(kc, kv_sharding)
        vc = jax.device_put(vc, kv_sharding)
    toks = jnp.asarray([5, 6, 7], jnp.int32)
    pos = jnp.asarray([10, 3, 0], jnp.int32)
    bt = jnp.tile(jnp.arange(1, 4, dtype=jnp.int32)[None, :], (B, 1))
    slots = bt[jnp.arange(B), pos // bs] * bs + pos % bs
    f = jax.jit(functools.partial(L.decode, run_params, c, mesh=mesh))
    lg, _, _ = f(toks, pos, kc, vc, bt, slots)
    return np.asarray(lg, np.float32)


@multichip
@pytest.mark.parametrize("quant", [False, True])
def test_meshed_fused_decode_token_parity_tp2(quant):
    cfg = _cfg()
    params = L.init_params(cfg, jax.random.PRNGKey(5), quantize=quant)
    mesh = build_mesh(tp=2, dp=1)
    a = _mesh_decode_once(cfg, params, mesh, fused=False)
    b = _mesh_decode_once(cfg, params, mesh, fused=True)
    np.testing.assert_allclose(a, b, atol=0.08, rtol=0)
    np.testing.assert_array_equal(a.argmax(-1), b.argmax(-1))


@multichip
@pytest.mark.parametrize("quant", [False, True])
def test_meshed_fused_decode_token_parity_tp4(quant):
    # tp=4 needs 4 kv heads for the Megatron head split
    cfg = _cfg(num_kv_heads=4)
    params = L.init_params(cfg, jax.random.PRNGKey(7), quantize=quant)
    mesh = build_mesh(tp=4, dp=1)
    a = _mesh_decode_once(cfg, params, mesh, fused=False)
    b = _mesh_decode_once(cfg, params, mesh, fused=True)
    np.testing.assert_allclose(a, b, atol=0.08, rtol=0)
    np.testing.assert_array_equal(a.argmax(-1), b.argmax(-1))


@multichip
def test_meshed_fused_decode_token_parity_dp_x_tp():
    """The serving mesh shape: dp x tp. The fused gate keys on the tp
    axis only; dp replicates the decode batch."""
    cfg = _cfg()
    params = L.init_params(cfg, jax.random.PRNGKey(9), quantize=True)
    mesh = build_mesh(tp=2, dp=2)
    a = _mesh_decode_once(cfg, params, mesh, fused=False)
    b = _mesh_decode_once(cfg, params, mesh, fused=True)
    np.testing.assert_allclose(a, b, atol=0.08, rtol=0)
    np.testing.assert_array_equal(a.argmax(-1), b.argmax(-1))


@multichip
@pytest.mark.parametrize("quant", [False, True])
def test_overlap_tail_token_identical_to_plain_psum(quant):
    """DYN_COLLECTIVE_OVERLAP: the decomposed collective-matmul tail vs
    the plain-psum meshed fused path. The ring reorders f32 adds, so the
    bar is allclose + greedy-token identity — overlap must never change
    what the engine emits."""
    cfg = _cfg()
    params = L.init_params(cfg, jax.random.PRNGKey(11), quantize=quant)
    mesh = build_mesh(tp=2, dp=1)
    a = _mesh_decode_once(cfg, params, mesh, fused=True, overlap=False)
    b = _mesh_decode_once(cfg, params, mesh, fused=True, overlap=True)
    np.testing.assert_allclose(a, b, atol=0.08, rtol=0)
    np.testing.assert_array_equal(a.argmax(-1), b.argmax(-1))


@multichip
def test_overlap_tail_matches_unfused_unmeshed_tokens():
    """End-to-end anchor: overlap-on meshed fused decode emits the same
    greedy tokens as the plain unfused single-device program."""
    cfg = _cfg()
    params = L.init_params(cfg, jax.random.PRNGKey(13), quantize=True)
    mesh = build_mesh(tp=2, dp=1)
    a = _mesh_decode_once(cfg, params, None, fused=False)
    b = _mesh_decode_once(cfg, params, mesh, fused=True, overlap=True)
    np.testing.assert_array_equal(a.argmax(-1), b.argmax(-1))


# ------------------------------------------------------- dispatch gating


@multichip
def test_meshed_dispatch_enters_fused_kernels():
    """Under a tp mesh with fused_decode on, every layer's decode step
    must trace through BOTH fused pallas programs (the old gate silently
    fell back unfused under any mesh — this pins the fix)."""
    cfg = _cfg()
    params = L.init_params(cfg, jax.random.PRNGKey(15))
    mesh = build_mesh(tp=2, dp=1)
    lin.reset_fused_kernel_entries()
    _mesh_decode_once(cfg, params, mesh, fused=True)
    assert lin.FUSED_KERNEL_ENTRIES["qkv_rope"] >= cfg.num_layers
    assert lin.FUSED_KERNEL_ENTRIES["attn_out"] >= cfg.num_layers
    lin.reset_fused_kernel_entries()
    _mesh_decode_once(cfg, params, mesh, fused=False)
    assert lin.FUSED_KERNEL_ENTRIES == {"qkv_rope": 0, "attn_out": 0}


@multichip
def test_indivisible_heads_gate_falls_back_unfused():
    """A tp axis that does not divide the kv heads (tiny has 2) must gate
    the fused dispatch OFF rather than mis-shard. (shard_llama refuses to
    even build such params, so the gate is the last line for hand-sharded
    callers.)"""
    cfg = dataclasses.replace(L.LlamaConfig.tiny(), fused_decode=True)
    params = L.init_params(cfg, jax.random.PRNGKey(17))
    layer = params["layers"][0]
    assert L._use_fused_decode(cfg, layer, build_mesh(tp=2, dp=1))
    assert not L._use_fused_decode(cfg, layer, build_mesh(tp=4, dp=1))


def test_overlap_gate_requires_mesh_and_divisibility():
    cfg = dataclasses.replace(
        L.LlamaConfig.tiny(), fused_decode=True, collective_overlap=True
    )
    params = L.init_params(cfg, jax.random.PRNGKey(19))
    layer = params["layers"][0]
    assert not L._use_overlap_tail(cfg, layer, None)
    if len(jax.devices()) >= 2:
        mesh = build_mesh(tp=2, dp=1)
        assert L._use_overlap_tail(cfg, layer, mesh)
        off = dataclasses.replace(cfg, collective_overlap=False)
        assert not L._use_overlap_tail(off, layer, mesh)


# --------------------------------------- int8 weights x int8 KV end-to-end


@multichip
def test_meshed_fused_decode_with_int8_kv_cache():
    """The full ISSUE 19 hot path: int8 weights + int8-resident paged KV
    + fused decode under a tp=2 mesh, greedy-identical to the unfused
    meshed program over a multi-step rollout."""
    from dynamo_tpu.engine.jax_engine.model_runner import ModelRunner

    cfg = L.LlamaConfig.tiny(vocab_size=64)
    params = L.init_params(cfg, jax.random.PRNGKey(0), quantize=True)
    mesh = build_mesh(tp=2, dp=1)
    sharded, kv_sharding = shard_llama(mesh, cfg, params)

    def run(fused, overlap=False):
        r = ModelRunner(
            cfg, sharded, num_blocks=64, block_size=4, max_batch=1,
            max_model_len=64, kv_dtype="int8", fused_decode=fused,
            collective_overlap=overlap, mesh=mesh, kv_sharding=kv_sharding,
        )
        blocks = list(range(1, 9))
        tables = np.zeros((1, r.max_blocks_per_seq), np.int32)
        tables[0, :8] = blocks
        out = r.fetch_sample(
            r.prefill(list(range(2, 12)), blocks, 0.0, 1.0, 0)
        )
        toks = [int(out[0])]
        pos = 9
        for _ in range(8):
            pos += 1
            slot = np.asarray([blocks[pos // 4] * 4 + pos % 4], np.int32)
            out = r.fetch_sample(
                r.decode(
                    np.asarray([toks[-1]], np.int32),
                    np.asarray([pos], np.int32), tables, slot,
                    np.zeros(1, np.float32), np.ones(1, np.float32),
                    np.zeros(1, np.int32),
                )
            )
            toks.append(int(out[0]))
        return toks

    base = run(False)
    assert base == run(True)
    assert base == run(True, overlap=True)


# --------------------------------------------------- factory block retune


async def test_factory_retunes_kv_block_size_for_int8(
    tmp_path, monkeypatch, caplog
):
    """DYN_KV_DTYPE=int8 with a sub-tile block size: the factory retunes
    to 32 (the Mosaic int8 (32, 128) sublane tile) with a warning instead
    of silently routing decode through the slow gather path."""
    from dynamo_tpu.engine.jax_engine.factory import build_jax_engine
    from tests.test_multihost import _tiny_model_dir

    model_dir = _tiny_model_dir(tmp_path)
    monkeypatch.setenv("DYN_KV_DTYPE", "int8")
    with caplog.at_level("WARNING"):
        engine, _ = await build_jax_engine(
            model_dir, name="t", kv_block_size=4, max_batch=2, num_blocks=16
        )
    try:
        assert engine.runner.block_size == 32
        assert any("retuning kv_block_size" in r.message for r in caplog.records)
    finally:
        await engine.close()
