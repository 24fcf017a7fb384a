"""Pallas flash kernels vs the XLA reference attention (interpret mode).

Mirrors the reference's kernel-correctness strategy (CUDA block_copy kernel
tested against plain copies): the XLA gather implementation is the oracle;
the pallas kernels must match it to bf16-friendly tolerance on ragged
context lengths, GQA and MHA head layouts, and non-pow2 batch sizes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops import attention as A
from dynamo_tpu.ops.basics import forms_traced
from dynamo_tpu.ops.pallas_attention import (
    flash_prefill_attention_pallas,
    paged_decode_attention_pallas,
)


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype=jnp.float32).astype(dtype)


@pytest.mark.parametrize("hq,hkv", [(8, 2), (4, 4), (16, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_matches_xla(hq, hkv, dtype):
    B, D, block_size, num_blocks, max_blocks = 3, 64, 16, 32, 4
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = _rand(keys[0], (B, hq, D), dtype)
    k_cache = _rand(keys[1], (hkv, num_blocks, block_size, D), dtype)
    v_cache = _rand(keys[2], (hkv, num_blocks, block_size, D), dtype)
    # distinct ragged context lens, block tables into scattered pages
    block_tables = jax.random.permutation(
        keys[3], num_blocks
    )[: B * max_blocks].reshape(B, max_blocks).astype(jnp.int32)
    context_lens = jnp.array([1, 17, 64], jnp.int32)

    ref = A.paged_decode_attention(q, k_cache, v_cache, block_tables, context_lens)
    out = paged_decode_attention_pallas(
        q, k_cache, v_cache, block_tables, context_lens, interpret=True
    )
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


# The grid since PR 29: one cell walks a lane and all of its KV heads, and a
# lane with a context of 0 holds no request. Block 8, two pages
# a chunk: contexts end inside a page (13, 49), on a page edge that is no
# chunk edge (24) and on a chunk edge (32); idle lanes lie between the live.
_LIVE = {1: 13, 3: 24, 4: 32, 6: 49}  # lane -> context, of 8 lanes


@pytest.mark.parametrize("hkv,group", [(8, 4), (4, 7), (2, 4), (1, 7)])
@pytest.mark.parametrize("window", [None, 20])
@pytest.mark.parametrize("softcap", [None, 5.0])
@pytest.mark.parametrize("cache", ["bf16", "int8"])
def test_paged_decode_lanes_and_heads_in_one_cell(hkv, group, window, softcap, cache):
    from dynamo_tpu.ops.kv_quant import quantize_blocks

    B, D, bs, nb, mb, W = 8, 32, 8, 40, 7, 2
    hq = hkv * group
    keys = jax.random.split(jax.random.PRNGKey(hkv * 10 + group), 4)
    q = _rand(keys[0], (B, hq, D), jnp.bfloat16)
    kc = _rand(keys[1], (hkv, nb, bs, D), jnp.bfloat16)
    vc = _rand(keys[2], (hkv, nb, bs, D), jnp.bfloat16)
    if cache == "int8":
        kc, vc = quantize_blocks(kc), quantize_blocks(vc)
    lens = np.zeros(B, np.int32)
    tables = np.zeros((B, mb), np.int32)  # an idle lane's table is zeros
    pages = np.asarray(jax.random.permutation(keys[3], nb - 1)) + 1
    for n, (lane, ctx) in enumerate(_LIVE.items()):
        lens[lane] = ctx
        tables[lane] = pages[n * mb:(n + 1) * mb]
    live = np.array(sorted(_LIVE))
    kw = dict(window=window, logit_softcap=softcap, scale=0.3)

    def pallas(q_, tables_, lens_):
        k_, v_ = (kc["q"], vc["q"]) if cache == "int8" else (kc, vc)
        scales = (
            dict(k_scales=kc["s"], v_scales=vc["s"]) if cache == "int8" else {}
        )
        return np.asarray(paged_decode_attention_pallas(
            q_, k_, v_, jnp.asarray(tables_), jnp.asarray(lens_),
            pages_per_chunk=W, interpret=True, **scales, **kw,
        ), np.float32)

    def xla(q_, tables_, lens_):
        return np.asarray(A.paged_decode_attention(
            q_, kc, vc, jnp.asarray(tables_), jnp.asarray(lens_), **kw
        ), np.float32)

    out, ref = pallas(q, tables, lens), xla(q, tables, lens)
    np.testing.assert_allclose(out[live], ref[live], atol=2e-2, rtol=2e-2)
    idle = [b for b in range(B) if b not in _LIVE]
    assert (out[idle] == 0).all() and (ref[idle] == 0).all()
    # the live lanes alone: the same rows, bit for bit, from both forms
    assert np.array_equal(pallas(q[live], tables[live], lens[live]), out[live])
    assert np.array_equal(xla(q[live], tables[live], lens[live]), ref[live])


@pytest.mark.parametrize("p,valid", [(32, 32), (64, 40), (128, 5)])
@pytest.mark.parametrize("hq,hkv", [(8, 2), (4, 4)])
def test_flash_prefill_matches_xla(p, valid, hq, hkv):
    D = 64
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(keys[0], (p, hq, D))
    k = _rand(keys[1], (p, hkv, D))
    v = _rand(keys[2], (p, hkv, D))
    vl = jnp.int32(valid)
    ref = A.causal_prefill_attention(q, k, v, vl)
    out = flash_prefill_attention_pallas(
        q, k, v, vl, block_q=32, block_k=32, interpret=True
    )
    # rows past valid_len are padding; the kernels may differ there
    np.testing.assert_allclose(
        np.asarray(out)[:valid], np.asarray(ref)[:valid], atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("pages_per_chunk", [2, 3])
def test_paged_decode_multichunk(pages_per_chunk):
    """Contexts spanning several DMA chunks: exercises the fori_loop
    double-buffer slot swap and the cross-chunk online-softmax rescale."""
    B, hq, hkv, D, block_size = 3, 8, 2, 64, 16
    num_blocks, max_blocks = 64, 12  # up to 6 chunks at W=2
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    q = _rand(keys[0], (B, hq, D))
    k_cache = _rand(keys[1], (hkv, num_blocks, block_size, D))
    v_cache = _rand(keys[2], (hkv, num_blocks, block_size, D))
    block_tables = jax.random.permutation(
        keys[3], num_blocks
    )[: B * max_blocks].reshape(B, max_blocks).astype(jnp.int32)
    # 1 chunk / several full chunks / partial last chunk
    context_lens = jnp.array([16, 192, 145], jnp.int32)
    ref = A.paged_decode_attention(q, k_cache, v_cache, block_tables, context_lens)
    out = paged_decode_attention_pallas(
        q, k_cache, v_cache, block_tables, context_lens,
        pages_per_chunk=pages_per_chunk, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_dispatcher_roundtrip(monkeypatch):
    """set_attention_impl routes the public API through the kernels."""
    B, hq, hkv, D, bs, nb, mb = 2, 4, 2, 32, 8, 8, 2
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    q = _rand(keys[0], (B, hq, D))
    kc = _rand(keys[1], (hkv, nb, bs, D))
    vc = _rand(keys[2], (hkv, nb, bs, D))
    bt = jnp.arange(B * mb, dtype=jnp.int32).reshape(B, mb)
    cl = jnp.array([5, 13], jnp.int32)
    ref = A.paged_decode_attention(q, kc, vc, bt, cl)
    A.set_attention_impl("pallas_interpret")
    try:
        out = A.paged_decode_attention(q, kc, vc, bt, cl)
    finally:
        A.set_attention_impl("xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)


def test_decode_under_jit():
    """Kernel must be jit-traceable (static grid from shapes only)."""
    B, hq, hkv, D, bs, nb, mb = 2, 4, 2, 32, 8, 8, 2
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q = _rand(keys[0], (B, hq, D))
    kc = _rand(keys[1], (hkv, nb, bs, D))
    vc = _rand(keys[2], (hkv, nb, bs, D))
    bt = jnp.arange(B * mb, dtype=jnp.int32).reshape(B, mb)
    cl = jnp.array([3, 9], jnp.int32)

    fn = jax.jit(
        lambda *a: paged_decode_attention_pallas(*a, interpret=True)
    )
    ref = A.paged_decode_attention(q, kc, vc, bt, cl)
    np.testing.assert_allclose(
        np.asarray(fn(q, kc, vc, bt, cl)), np.asarray(ref), atol=1e-5, rtol=1e-5
    )


def test_untileable_shapes_fall_back_to_xla():
    """head_dim 64 / block_size 4 can't satisfy Mosaic VMEM tiling on real
    TPU (r04 verify: 'Slice shape ... must be aligned to tiling'); with
    impl='pallas' the dispatch must route to the XLA path instead of
    attempting the kernel. On CPU a non-interpret pallas call would fail
    outright, so these succeeding proves the fallback fired."""
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    B, hq, hkv, D, bs, nb = 2, 4, 2, 64, 4, 16
    q = _rand(keys[0], (B, hq, D))
    kc = _rand(keys[1], (hkv, nb, bs, D))
    vc = _rand(keys[2], (hkv, nb, bs, D))
    bt = jnp.tile(jnp.arange(4, dtype=jnp.int32), (B, 1))
    cl = jnp.array([3, 9], jnp.int32)
    out = A.paged_decode_attention(q, kc, vc, bt, cl, impl="pallas")
    ref = A.paged_decode_attention(q, kc, vc, bt, cl, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)

    p = _rand(keys[3], (32, hq, D))
    k1 = _rand(keys[1], (32, hkv, D))
    v1 = _rand(keys[2], (32, hkv, D))
    o2 = A.causal_prefill_attention(p, k1, v1, jnp.int32(20), impl="pallas")
    r2 = A.causal_prefill_attention(p, k1, v1, jnp.int32(20), impl="xla")
    np.testing.assert_allclose(np.asarray(o2), np.asarray(r2), atol=1e-6)


def test_runner_untileable_config_downgrades_to_xla():
    from dynamo_tpu.engine.jax_engine.model_runner import ModelRunner
    from dynamo_tpu.models import llama as L

    cfg = L.LlamaConfig.tiny(vocab_size=64)  # head_dim < 128
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    runner = ModelRunner(
        cfg, params, num_blocks=16, block_size=8, max_batch=2,
        max_model_len=64, attn_impl="pallas",
    )
    assert runner.attn_impl == "xla"


# ---------------------------------------------- 64-wide heads in pairs (PR 44)


def _rows(cache, pack):
    """A cache of a head a row `[Hkv, nb, bs, D]` as stored rows of `pack`
    heads side by side `[Hkv / pack, nb, bs, pack * D]`."""
    hkv, nb, bs, d = cache.shape
    paired = jnp.moveaxis(cache.reshape(hkv // pack, pack, nb, bs, d), 1, 3)
    return paired.reshape(hkv // pack, nb, bs, pack * d)


@pytest.mark.parametrize("hq,hkv,D,pack", [(32, 8, 64, 2), (8, 4, 64, 2), (8, 4, 32, 4), (4, 2, 64, 2)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_over_paired_rows_matches_xla(hq, hkv, D, pack, dtype):
    """Heads narrower than a tile's lanes, cached `pack` to a row: the
    public call widens the queries, runs the kernel on heads of `pack * D`
    and keeps each head's own lanes; it gives what the XLA form gives on the
    same rows, and both give what a cache of a head a row gives. An idle lane
    gets zeros; the scale stays the narrow head's."""
    B, block_size, num_blocks, max_blocks = 5, 16, 40, 6
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    q = _rand(keys[0], (B, hq, D), dtype)
    k_cache = _rand(keys[1], (hkv, num_blocks, block_size, D), dtype)
    v_cache = _rand(keys[2], (hkv, num_blocks, block_size, D), dtype)
    tables = jax.random.permutation(keys[3], num_blocks)[: B * max_blocks].reshape(B, max_blocks).astype(jnp.int32)
    lens = jnp.array([0, 1, 17, 64, 96], jnp.int32)
    by_head = A.paged_decode_attention(q, k_cache, v_cache, tables, lens, impl="xla")
    kr, vr = _rows(k_cache, pack), _rows(v_cache, pack)
    assert kr.shape[-1] == pack * D and kr.size == k_cache.size  # the same bytes
    xla = A.paged_decode_attention(q, kr, vr, tables, lens, impl="xla")
    np.testing.assert_array_equal(np.asarray(xla, np.float32), np.asarray(by_head, np.float32))
    out = A.paged_decode_attention(q, kr, vr, tables, lens, impl="pallas_interpret")
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(by_head, np.float32), atol=tol, rtol=tol
    )
    assert (np.asarray(out, np.float32)[0] == 0).all()
    scaled = A.paged_decode_attention(q, kr, vr, tables, lens, impl="pallas_interpret", scale=0.05)
    want = A.paged_decode_attention(q, k_cache, v_cache, tables, lens, impl="xla", scale=0.05)
    np.testing.assert_allclose(
        np.asarray(scaled, np.float32), np.asarray(want, np.float32), atol=tol, rtol=tol
    )


@pytest.mark.parametrize("p,valid", [(32, 32), (64, 40), (128, 5)])
@pytest.mark.parametrize("hq,hkv,D,pack", [(32, 8, 64, 2), (8, 4, 32, 4)])
def test_flash_prefill_over_paired_rows_matches_xla(p, valid, hq, hkv, D, pack):
    """Keys and values handed to prefill attention as stored rows (the free
    reshape of what the projections produce): the flash kernel on widened
    queries against the XLA form by head."""
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    q = _rand(keys[0], (p, hq, D))
    k = _rand(keys[1], (p, hkv, D))
    v = _rand(keys[2], (p, hkv, D))
    vl = jnp.int32(valid)
    ref = A.causal_prefill_attention(q, k, v, vl, impl="xla")
    kr, vr = k.reshape(p, hkv // pack, pack * D), v.reshape(p, hkv // pack, pack * D)
    xla = A.causal_prefill_attention(q, kr, vr, vl, impl="xla")
    np.testing.assert_array_equal(np.asarray(xla)[:valid], np.asarray(ref)[:valid])
    out = A.causal_prefill_attention(q, kr, vr, vl, impl="pallas_interpret")
    np.testing.assert_allclose(
        np.asarray(out)[:valid], np.asarray(ref)[:valid], atol=2e-5, rtol=2e-5
    )


def test_chunked_prefill_reads_paired_rows():
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    hq, hkv, D, pack, nb, bs = 8, 4, 64, 2, 24, 8
    q = _rand(keys[0], (16, hq, D))
    k_cache = _rand(keys[1], (hkv, nb, bs, D))
    v_cache = _rand(keys[2], (hkv, nb, bs, D))
    table = jax.random.permutation(keys[3], nb)[:6].astype(jnp.int32)
    ref = A.chunked_prefill_attention(q, k_cache, v_cache, table, jnp.int32(24))
    out = A.chunked_prefill_attention(q, _rows(k_cache, pack), _rows(v_cache, pack), table, jnp.int32(24))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_a_shape_that_falls_to_xla_says_so_once(caplog):
    """`impl="pallas"` on rows the kernel cannot tile (64-wide heads a head a
    row, as the grouped-query family caches them) is served by the XLA form
    and the log says so, once a shape; rows that do not hold whole heads are
    refused."""
    import logging

    keys = jax.random.split(jax.random.PRNGKey(6), 3)
    B, hq, hkv, D, bs, nb = 2, 4, 2, 64, 16, 16
    q = _rand(keys[0], (B, hq, D))
    kc = _rand(keys[1], (hkv, nb, bs, D))
    vc = _rand(keys[2], (hkv, nb, bs, D))
    bt = jnp.tile(jnp.arange(4, dtype=jnp.int32), (B, 1))
    cl = jnp.array([3, 9], jnp.int32)
    A._said.clear()
    with caplog.at_level(logging.WARNING, logger="dynamo_tpu.ops.attention"):
        for _ in range(3):
            out = A.paged_decode_attention(q, kc, vc, bt, cl, impl="pallas")
    ref = A.paged_decode_attention(q, kc, vc, bt, cl, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)
    said = [r for r in caplog.records if "cannot be tiled" in r.getMessage()]
    assert len(said) == 1 and "rows of 64 values" in said[0].getMessage()
    assert A._pallas_tileable(128, 16) and not A._pallas_tileable(64, 16)
    with pytest.raises(ValueError, match="do not hold whole heads"):
        A.paged_decode_attention(q, _rand(keys[1], (1, nb, bs, 96)), vc, bt, cl)


# ------------------------- the append inside the decode kernel (PR 47)
#
# `A.decode_append_attention`'s kernel form against the pair it stands for,
# `write_decode_kv` then `paged_decode_attention` in the XLA form. Pages of
# 16 tokens, eight a chunk; lane -> context INCLUDING the new token, 0 = idle.

_APPEND_CASES = {
    "8 of 32 heads": dict(hq=32, hkv=8),
    "4 of 28 heads": dict(hq=28, hkv=4),
    "1 of 20 heads": dict(hq=20, hkv=1),
    "paired 64-wide heads": dict(hq=32, hkv=8, D=64, pack=2),
    "a window shorter than the context": dict(window=24, lens=(70, 150, 25, 9)),
    "a softcap": dict(softcap=5.0),
    "a context of 1": dict(lens=(1, 1, 40, 1)),
    "a write at offset 0": dict(lens=(17, 33, 1, 129)),
    "a write at offset 15": dict(lens=(16, 32, 128, 144)),
    "the new token opens a chunk": dict(lens=(8 * 16 + 1, 2 * 8 * 16 + 1, 5, 8 * 16)),
    "idle lanes among live ones": dict(lens=(0, 37, 0, 0, 130, 0, 16, 0)),
}


def _append_inputs(dtype, hq=8, hkv=2, D=32, pack=1, lens=(37, 70, 16, 131), seed=0):
    bs, W = 16, 8
    B, mb = len(lens), max(-(-max(lens) // bs), 1) + 1
    nb = 1 + sum(-(-n // bs) for n in lens) + 3  # null, the lanes', three no one's
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = _rand(keys[0], (B, hq, D), dtype)
    stored = (hkv // pack, nb, bs, pack * D)
    k_cache, v_cache = _rand(keys[1], stored, dtype), _rand(keys[2], stored, dtype)
    k_new = _rand(keys[3], (B, hkv // pack, pack * D), dtype)
    v_new = _rand(keys[4], (B, hkv // pack, pack * D), dtype)
    # zeros of both signs among the new values: the caches are held to bits
    k_new = k_new.at[:, :, 0].set(-0.0).at[:, :, 1].set(0.0)
    pages = np.asarray(jax.random.permutation(keys[5], nb - 1)) + 1
    tables, slots, at = np.zeros((B, mb), np.int32), np.zeros(B, np.int32), 0
    for b, n in enumerate(lens):  # an idle lane: a table of zeros, slot 0
        used = -(-n // bs)
        tables[b, :used] = pages[at:at + used]
        at += used
        if n:
            slots[b] = tables[b, (n - 1) // bs] * bs + (n - 1) % bs
    return (
        q, k_cache, v_cache, k_new, v_new, jnp.asarray(slots),
        jnp.asarray(tables), jnp.asarray(lens, jnp.int32),
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(_APPEND_CASES))
def test_decode_append_inside_the_kernel_is_the_pair(case, dtype):
    """The caches hold, bit for bit, what the scatter wrote in every block a
    live lane owns; the null block and every block no lane owns are as they
    were (the pair writes an idle lane's row into the null block: the one
    place the two differ); the rows that changed are the live lanes' slots
    and no others; the attention is the pair's within the file's tolerance,
    and an idle lane's is zeros."""
    spec = dict(_APPEND_CASES[case])
    kw = dict(window=spec.pop("window", None), logit_softcap=spec.pop("softcap", None))
    inputs = _append_inputs(dtype, **spec)
    q, k_cache, v_cache, k_new, v_new, slots, tables, lens = inputs
    with forms_traced() as counted:
        out, k_got, v_got = A.decode_append_attention(*inputs, impl="pallas_interpret", **kw)
    assert counted == {"kv_append_folded": 1}
    k_ref, v_ref = A.write_decode_kv(k_cache, v_cache, k_new, v_new, slots)
    ref = A.paged_decode_attention(q, k_ref, v_ref, tables, lens, impl="xla", **kw)

    live = np.asarray(lens) > 0
    bs = k_cache.shape[2]
    for got, want, was in ((k_got, k_ref, k_cache), (v_got, v_ref, v_cache)):
        got, want, was = (np.asarray(x, np.float32) for x in (got, want, was))
        assert np.array_equal(got[:, 1:], want[:, 1:])
        assert np.array_equal(np.signbit(got[:, 1:]), np.signbit(want[:, 1:]))
        assert np.array_equal(got[:, 0], was[:, 0])  # the null block
        changed = np.argwhere((got != was).any(axis=(0, 3)))  # (block, offset)
        assert sorted(int(b * bs + o) for b, o in changed) == sorted(
            int(s) for s in np.asarray(slots)[live]
        )
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    np.testing.assert_allclose(out[live], ref[live], atol=tol, rtol=tol)
    assert (out[~live] == 0).all()


@pytest.mark.parametrize("form", ["an int8-resident cache", "the XLA form", "an untileable shape"])
def test_decode_append_falls_to_the_pair_by_what_it_sees(form):
    """No flag: an int8-resident cache (an append regrows a block's scale),
    `impl="xla"`, and rows the kernel cannot tile go through the entry to
    `write_decode_kv` and `paged_decode_attention` and give what they give,
    the null block's row with it."""
    from dynamo_tpu.ops.kv_quant import quantize_blocks

    inputs = _append_inputs(
        jnp.bfloat16, lens=(37, 0, 16, 131), D=64 if form == "an untileable shape" else 32
    )
    q, k_cache, v_cache, k_new, v_new, slots, tables, lens = inputs
    impl = {"an int8-resident cache": "pallas_interpret", "the XLA form": "xla", "an untileable shape": "pallas"}[form]
    if form == "an int8-resident cache":
        k_cache, v_cache = quantize_blocks(k_cache), quantize_blocks(v_cache)
    with forms_traced() as counted:
        out, k_got, v_got = A.decode_append_attention(
            q, k_cache, v_cache, k_new, v_new, slots, tables, lens, impl=impl
        )
    assert counted == {"kv_append_scattered": 1}
    k_ref, v_ref = A.write_decode_kv(k_cache, v_cache, k_new, v_new, slots)
    ref = A.paged_decode_attention(q, k_ref, v_ref, tables, lens, impl=impl)
    same = lambda a, b: np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
    assert same(out, ref)
    assert jax.tree.all(jax.tree.map(same, (k_got, v_got), (k_ref, v_ref)))


def test_decode_append_under_jit_with_the_caches_donated():
    """As a step program calls it: jitted, the two caches donated, a second
    step on the first one's result."""
    inputs = _append_inputs(jnp.bfloat16, lens=(15, 0, 31, 130))
    q, k_cache, v_cache, k_new, v_new, slots, tables, lens = inputs
    step = jax.jit(
        lambda k, v, n, s: A.decode_append_attention(
            q, k, v, k_new, v_new, s, tables, n, impl="pallas_interpret"
        ), donate_argnums=(0, 1),
    )
    live = lens > 0
    k_ref, v_ref, outs, refs = k_cache, v_cache, [], []
    k_got, v_got = jnp.copy(k_cache), jnp.copy(v_cache)
    for _ in range(2):
        out, k_got, v_got = step(k_got, v_got, lens, slots)
        k_ref, v_ref = A.write_decode_kv(k_ref, v_ref, k_new, v_new, slots)
        refs.append(A.paged_decode_attention(q, k_ref, v_ref, tables, lens, impl="xla"))
        outs.append(out)
        lens, slots = jnp.where(live, lens + 1, 0), jnp.where(live, slots + 1, 0)
    assert np.array_equal(np.asarray(k_got[:, 1:], np.float32), np.asarray(k_ref[:, 1:], np.float32))
    assert np.array_equal(np.asarray(v_got[:, 1:], np.float32), np.asarray(v_ref[:, 1:], np.float32))
    for out, ref in zip(outs, refs):
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-2, rtol=2e-2
        )
