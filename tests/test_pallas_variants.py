"""Pallas kernel feature parity: {window, softcap, scale} across all three
programs (prefill, paged decode, spec verify), pallas-interpret vs the XLA
gather oracle, over GQA ratios 1/4/8 — the tier-1 proof that sliding-window
and soft-capped families (Mistral, Gemma 2/3) run the flash path exactly.

Also the end-to-end half: a Gemma-3-pattern model (5:1 local:global layer
mix) decoding with attn_impl="pallas_interpret" must route EVERY layer —
local and global — through the pallas kernels (counted by monkeypatching
the kernel entry points), matching the XLA-impl logits bit-for-bit in f32
tolerance. Before this suite, ops/attention.py silently punted any layer
with window/scale/softcap to the XLA gather fallback.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.util import layer_caches
from dynamo_tpu.ops import attention as A
from dynamo_tpu.ops import pallas_attention as PA


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype=jnp.float32).astype(dtype)


# (window, scale, logit_softcap) — each feature alone plus the Gemma2-like
# combination; window=1 is the degenerate self-only edge
VARIANTS = [
    pytest.param(None, None, None, id="full"),
    pytest.param(40, None, None, id="window"),
    pytest.param(1, None, None, id="window1"),
    pytest.param(None, 0.35, None, id="scale"),
    pytest.param(None, None, 30.0, id="softcap"),
    pytest.param(24, 0.35, 20.0, id="window+scale+softcap"),
]

GQA = [pytest.param(8, 8, id="gqa1"), pytest.param(8, 2, id="gqa4"),
       pytest.param(16, 2, id="gqa8")]


@pytest.mark.parametrize("window,scale,softcap", VARIANTS)
@pytest.mark.parametrize("hq,hkv", GQA)
def test_decode_variant_parity(window, scale, softcap, hq, hkv):
    B, D, bs, nb, mb = 3, 64, 16, 64, 12
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = _rand(keys[0], (B, hq, D))
    kc = _rand(keys[1], (hkv, nb, bs, D))
    vc = _rand(keys[2], (hkv, nb, bs, D))
    bt = jax.random.permutation(keys[3], nb)[: B * mb].reshape(B, mb).astype(
        jnp.int32
    )
    # one-chunk, multi-chunk, and partial-chunk contexts
    cl = jnp.array([16, 192, 145], jnp.int32)
    ref = A.paged_decode_attention(
        q, kc, vc, bt, cl,
        window=window, scale=scale, logit_softcap=softcap, impl="xla",
    )
    out = A.paged_decode_attention(
        q, kc, vc, bt, cl,
        window=window, scale=scale, logit_softcap=softcap,
        impl="pallas_interpret",
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("window,scale,softcap", VARIANTS)
@pytest.mark.parametrize("hq,hkv", GQA)
@pytest.mark.parametrize("valid", [128, 77, 5])
def test_prefill_variant_parity(window, scale, softcap, hq, hkv, valid):
    P, D = 128, 64
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(keys[0], (P, hq, D))
    k = _rand(keys[1], (P, hkv, D))
    v = _rand(keys[2], (P, hkv, D))
    vl = jnp.int32(valid)
    ref = A.causal_prefill_attention(
        q, k, v, vl,
        window=window, scale=scale, logit_softcap=softcap, impl="xla",
    )
    out = A.causal_prefill_attention(
        q, k, v, vl,
        window=window, scale=scale, logit_softcap=softcap,
        impl="pallas_interpret",
    )
    np.testing.assert_allclose(
        np.asarray(out)[:valid], np.asarray(ref)[:valid], atol=3e-5, rtol=3e-5
    )


@pytest.mark.parametrize("window,scale,softcap", VARIANTS)
@pytest.mark.parametrize("hq,hkv", GQA)
def test_verify_variant_parity(window, scale, softcap, hq, hkv):
    B, S, D, bs, nb, mb = 3, 4, 64, 16, 64, 12
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    q = _rand(keys[0], (B, S, hq, D))
    kc = _rand(keys[1], (hkv, nb, bs, D))
    vc = _rand(keys[2], (hkv, nb, bs, D))
    bt = jax.random.permutation(keys[3], nb)[: B * mb].reshape(B, mb).astype(
        jnp.int32
    )
    # draft windows straddling chunk boundaries at ragged depths
    base = jnp.array([3, 100, 140], jnp.int32)
    pos = base[:, None] + jnp.arange(S)[None, :]
    ref = A.paged_verify_attention(
        q, kc, vc, bt, pos,
        window=window, scale=scale, logit_softcap=softcap, impl="xla",
    )
    out = A.paged_verify_attention(
        q, kc, vc, bt, pos,
        window=window, scale=scale, logit_softcap=softcap,
        impl="pallas_interpret",
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_decode_window_skips_leading_chunks():
    """The O(window) traffic claim at the kernel-arithmetic level: the
    chunk range the kernel iterates (and DMAs) must not grow with context
    once context > window."""
    for ctx in (256, 1024, 8192, 65536):
        full = PA.decode_kv_chunks_read(ctx, block_size=16, pages_per_chunk=8)
        win = PA.decode_kv_chunks_read(
            ctx, block_size=16, pages_per_chunk=8, window=128
        )
        assert win <= 2  # window + chunk-alignment slop, never O(ctx)
        assert full == -(-ctx // 128)
    # and the window bound is tight: ceil(window / chunk) chunks when the
    # window lands chunk-aligned, +1 alignment slop otherwise
    assert PA.decode_kv_chunks_read(
        4096, block_size=16, pages_per_chunk=8, window=1024
    ) == 8
    assert PA.decode_kv_chunks_read(
        4095, block_size=16, pages_per_chunk=8, window=1024
    ) == 9


# --------------------------------------------- end-to-end mixed-pattern


class _KernelCounter:
    """Counts trace-time entries into each pallas kernel program."""

    def __init__(self, monkeypatch):
        self.counts = {"prefill": 0, "decode": 0, "verify": 0}
        real = {
            "prefill": PA.flash_prefill_attention_pallas,
            "decode": PA.paged_decode_attention_pallas,
            "verify": PA.paged_verify_attention_pallas,
        }

        def wrap(name):
            def inner(*a, **kw):
                self.counts[name] += 1
                return real[name](*a, **kw)

            return inner

        for name, attr in (
            ("prefill", "flash_prefill_attention_pallas"),
            ("decode", "paged_decode_attention_pallas"),
            ("verify", "paged_verify_attention_pallas"),
        ):
            monkeypatch.setattr(PA, attr, wrap(name))


def _gemma3_tiny():
    """Tiny Gemma-3-shaped config via the real HF detection path: 6 layers
    in the 5 local : 1 global pattern, local rope theta, qk-norm, custom
    query scale."""
    from dynamo_tpu.models import llama as L

    return L.LlamaConfig.from_hf_dict(
        {
            "model_type": "gemma3_text",
            "vocab_size": 128,
            "hidden_size": 64,
            "intermediate_size": 128,
            "num_hidden_layers": 6,
            "num_attention_heads": 4,
            "num_key_value_heads": 2,
            "head_dim": 16,
            "rope_theta": 1_000_000.0,
            "rope_local_base_freq": 10_000.0,
            "sliding_window": 16,
            "sliding_window_pattern": 6,
            "query_pre_attn_scalar": 16.0,
            "max_position_embeddings": 256,
        }
    )


def test_gemma3_pattern_end_to_end_all_layers_flash(monkeypatch):
    """A 5:1 local:global Gemma-3 model under attn_impl='pallas_interpret':
    every layer — sliding AND global — must take the flash path in both
    prefill and paged decode, and the logits must match the XLA impl."""
    from dynamo_tpu.models import llama as L

    cfg = _gemma3_tiny()
    assert cfg.layer_pattern == (True,) * 5 + (False,)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    bs, nb, P = 8, 16, 32
    cache_shape = (cfg.num_layers, cfg.num_kv_heads, nb, bs, cfg.head_dim)

    def run(impl):
        kc = layer_caches(cache_shape, jnp.float32)
        vc = layer_caches(cache_shape, jnp.float32)
        c = dataclasses.replace(cfg, attn_impl=impl)
        tokens = jnp.arange(P, dtype=jnp.int32) % cfg.vocab_size
        table = jnp.arange(1, 1 + P // bs, dtype=jnp.int32)
        logits_p, kc, vc = L.prefill(
            params, c, tokens, jnp.int32(P), kc, vc, table
        )
        # one decode step for a 2-lane batch on top of the same prompt
        # (the lanes share the prompt's pages and open a page each: two
        # lanes never write one slot, and where they did the scatter let the
        # last one win while the kernel's append keeps each lane's own row)
        bt = jnp.tile(
            jnp.arange(1, 1 + nb - 1, dtype=jnp.int32)[None, :], (2, 1)
        ).at[1, P // bs].set(nb - 1)
        positions = jnp.array([P, P], jnp.int32)
        slots = bt[jnp.arange(2), positions // bs] * bs + positions % bs
        logits_d, kc, vc = L.decode(
            params, c,
            jnp.array([5, 7], jnp.int32),
            positions,
            kc, vc, bt, slots,
        )
        return logits_p, logits_d

    counter = _KernelCounter(monkeypatch)
    # eagerly, so that every layer's body runs here and is counted (under
    # jit a layer is a call of one of two cached bodies: models.layer_body)
    with jax.disable_jit():
        out_p, out_d = run("pallas_interpret")
    # every layer traced through the kernels — no silent XLA fallback
    assert counter.counts["prefill"] == cfg.num_layers
    assert counter.counts["decode"] == cfg.num_layers
    with jax.disable_jit():  # as the kernels' run: bfloat16 rounds alike
        ref_p, ref_d = run("xla")
    np.testing.assert_allclose(
        np.asarray(out_p), np.asarray(ref_p), atol=2e-4, rtol=2e-4
    )
    np.testing.assert_allclose(
        np.asarray(out_d), np.asarray(ref_d), atol=2e-4, rtol=2e-4
    )


def test_gemma3_pattern_verify_all_layers_flash(monkeypatch):
    """decode_verify (the spec-decode weight pass) on the same mixed
    pattern: every layer's verify attention must be pallas."""
    from dynamo_tpu.models import llama as L

    cfg = _gemma3_tiny()
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    bs, nb, B, S = 8, 16, 2, 3
    cache_shape = (cfg.num_layers, cfg.num_kv_heads, nb, bs, cfg.head_dim)

    def run(impl):
        kc = layer_caches(cache_shape, jnp.float32)
        vc = layer_caches(cache_shape, jnp.float32)
        c = dataclasses.replace(cfg, attn_impl=impl)
        bt = jnp.stack(
            [jnp.arange(1, nb, dtype=jnp.int32),
             jnp.arange(1, nb, dtype=jnp.int32)]
        )
        tokens = jnp.array([[3, 4, 5], [6, 7, 8]], jnp.int32)
        positions = jnp.array([[4, 5, 6], [9, 10, 11]], jnp.int32)
        rows = jnp.arange(B)[:, None]
        slots = bt[rows, positions // bs] * bs + positions % bs
        logits, kc, vc = L.decode_verify(
            params, c, tokens, positions, kc, vc, bt, slots
        )
        return logits

    counter = _KernelCounter(monkeypatch)
    with jax.disable_jit():  # every layer's body runs here and is counted
        out = run("pallas_interpret")
    assert counter.counts["verify"] == cfg.num_layers
    with jax.disable_jit():
        ref = run("xla")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-4, rtol=2e-4
    )
