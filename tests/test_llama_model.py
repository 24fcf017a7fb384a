"""Model math correctness: prefill/decode consistency over the paged cache,
int8 quantization sanity, sampling ops."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.util import layer_caches
from dynamo_tpu.models import llama as L
from dynamo_tpu.ops.linear import linear, quantize_int8
from dynamo_tpu.ops.sampling import sample_tokens


@pytest.fixture(scope="module")
def tiny_setup():
    cfg = L.LlamaConfig.tiny(vocab_size=64)
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _empty_cache(cfg, num_blocks=32, block_size=4):
    shape = (cfg.num_layers, cfg.num_kv_heads, num_blocks, block_size, cfg.head_dim)
    return layer_caches(shape, jnp.bfloat16), layer_caches(shape, jnp.bfloat16)


def test_prefill_decode_consistency(tiny_setup):
    """Logits from [prefill T tokens + decode K steps] must match a single
    full prefill over T+K tokens — the paged cache is exact, not approximate."""
    cfg, params = tiny_setup
    kc, vc = _empty_cache(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (13,), 0, 64)
    table = jnp.array([1, 2, 3, 4], jnp.int32)  # block 0 is the null block

    def pad(a, n):
        return jnp.concatenate([a, jnp.zeros(n - a.shape[0], a.dtype)])

    logits_full, _, _ = L.prefill(
        params, cfg, pad(toks, 16), jnp.int32(13), kc, vc, table
    )
    _, kc2, vc2 = L.prefill(
        params, cfg, pad(toks[:9], 16), jnp.int32(9), kc, vc, table
    )
    bt = jnp.zeros((1, 8), jnp.int32).at[0, :4].set(table)
    logits_d = None
    for i in range(9, 13):
        slot = table[i // 4] * 4 + i % 4
        logits_d, kc2, vc2 = L.decode(
            params, cfg, toks[i][None], jnp.array([i], jnp.int32),
            kc2, vc2, bt, slot[None],
        )
    np.testing.assert_allclose(
        np.asarray(logits_full), np.asarray(logits_d[0]), atol=1e-2, rtol=1e-2
    )


def test_batched_decode_isolation(tiny_setup):
    """Two sequences in one decode batch must not contaminate each other:
    batch-of-2 logits == each sequence decoded alone."""
    cfg, params = tiny_setup
    kc, vc = _empty_cache(cfg)
    t_a = jax.random.randint(jax.random.PRNGKey(2), (7,), 0, 64)
    t_b = jax.random.randint(jax.random.PRNGKey(3), (5,), 0, 64)

    def pad(a, n):
        return jnp.concatenate([a, jnp.zeros(n - a.shape[0], a.dtype)])

    tab_a = jnp.array([1, 2], jnp.int32)
    tab_b = jnp.array([3, 4], jnp.int32)
    _, kc1, vc1 = L.prefill(params, cfg, pad(t_a, 8), jnp.int32(7), kc, vc, tab_a)
    _, kc1, vc1 = L.prefill(params, cfg, pad(t_b, 8), jnp.int32(5), kc1, vc1, tab_b)
    bt = jnp.zeros((2, 8), jnp.int32)
    bt = bt.at[0, :2].set(tab_a).at[1, :2].set(tab_b)
    toks = jnp.array([t_a[-1], t_b[-1]], jnp.int32)  # dummy next inputs
    new_a, new_b = jnp.int32(11), jnp.int32(22)
    positions = jnp.array([7, 5], jnp.int32)
    slots = jnp.array([1 * 4 + 3, 4 * 4 + 1], jnp.int32)
    logits_pair, _, _ = L.decode(
        params, cfg, jnp.array([new_a, new_b]), positions, kc1, vc1, bt, slots
    )
    # sequence A alone
    logits_a, _, _ = L.decode(
        params, cfg, new_a[None], positions[:1], kc1, vc1, bt[:1], slots[:1]
    )
    np.testing.assert_allclose(
        np.asarray(logits_pair[0]), np.asarray(logits_a[0]), atol=1e-2, rtol=1e-2
    )


def test_int8_quantized_linear_close():
    rng = jax.random.PRNGKey(0)
    w = jax.random.normal(rng, (64, 32), jnp.float32) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64), jnp.bfloat16)
    exact = jnp.matmul(x, w.astype(jnp.bfloat16))
    quant = linear(x, quantize_int8(w))
    err = jnp.abs(exact.astype(jnp.float32) - quant.astype(jnp.float32)).max()
    scale = jnp.abs(exact).max()
    assert err / scale < 0.05


def test_quantized_model_runs(tiny_setup):
    cfg, _ = tiny_setup
    params_q = L.init_params(cfg, jax.random.PRNGKey(0), quantize=True)
    kc, vc = _empty_cache(cfg)
    toks = jnp.arange(4, dtype=jnp.int32)
    logits, _, _ = L.prefill(
        params_q, cfg, toks, jnp.int32(4), kc, vc, jnp.array([1], jnp.int32)
    )
    assert logits.shape == (cfg.vocab_size,)
    assert bool(jnp.isfinite(logits).all())


def test_sampling_modes():
    logits = jnp.asarray(
        np.log(np.array([[0.05, 0.6, 0.3, 0.05], [0.25, 0.25, 0.25, 0.25]]))
    ).astype(jnp.float32)
    key = jax.random.PRNGKey(0)
    # greedy (temperature 0)
    toks = sample_tokens(
        logits, key,
        temperature=jnp.array([0.0, 0.0]),
        top_p=jnp.array([1.0, 1.0]),
        top_k=jnp.array([0, 0]),
    )
    assert int(toks[0]) == 1
    # top_p=0.6 on row 0 keeps only token 1
    for seed in range(5):
        t = sample_tokens(
            logits, jax.random.PRNGKey(seed),
            temperature=jnp.array([1.0, 1.0]),
            top_p=jnp.array([0.5, 1.0]),
            top_k=jnp.array([0, 0]),
        )
        assert int(t[0]) == 1
    # top_k=1 behaves like greedy
    for seed in range(5):
        t = sample_tokens(
            logits, jax.random.PRNGKey(seed),
            temperature=jnp.array([1.0, 1.0]),
            top_p=jnp.array([1.0, 1.0]),
            top_k=jnp.array([1, 1]),
        )
        assert int(t[0]) == 1


def test_chunked_prefill_matches_single_shot(tiny_setup):
    """Chunked prefill (vLLM-style, VERDICT round-1 item) must produce the
    same final logits and cache contents as one single-shot prefill."""
    cfg, params = tiny_setup
    kc, vc = _empty_cache(cfg)
    T, C = 13, 8  # 13 tokens in chunks of 8 -> 2 chunks, ragged tail
    toks = jax.random.randint(jax.random.PRNGKey(5), (T,), 0, 64)
    table = jnp.array([1, 2, 3, 4], jnp.int32)

    padded = jnp.concatenate([toks, jnp.zeros(16 - T, toks.dtype)])
    logits_full, kc_ref, vc_ref = L.prefill(
        params, cfg, padded, jnp.int32(T), kc, vc, table
    )

    kc2, vc2 = _empty_cache(cfg)
    max_table = jnp.zeros(8, jnp.int32).at[:4].set(table)
    logits_chunk = None
    for start in range(0, T, C):
        chunk = toks[start : start + C]
        chunk = jnp.concatenate(
            [chunk, jnp.zeros(C - chunk.shape[0], toks.dtype)]
        )
        logits_chunk, kc2, vc2 = L.prefill_chunk(
            params, cfg, chunk, jnp.int32(start), jnp.int32(T),
            kc2, vc2, max_table,
        )
    np.testing.assert_allclose(
        np.asarray(logits_full), np.asarray(logits_chunk), atol=1e-2, rtol=1e-2
    )
    # cache contents agree on the used blocks (valid token positions)
    used = np.asarray(table)
    k_ref = np.stack(kc_ref)[:, :, used].astype(np.float32).reshape(-1, 16, cfg.head_dim)
    k_new = np.stack(kc2)[:, :, used].astype(np.float32).reshape(-1, 16, cfg.head_dim)
    np.testing.assert_allclose(k_ref[:, :T], k_new[:, :T], atol=1e-2, rtol=1e-2)


def test_chunked_prefill_ragged_table_no_clamp(tiny_setup):
    """Regression: a final chunk whose padded tail extends past the block
    table must not clamp backwards and overwrite earlier blocks' KV
    (dynamic_slice clamping — round-2 review finding). Table width 3
    (11-token prompt, bs=4) with 8-token chunks puts chunk 2 at start
    block 2 needing 2 entries — past the table without the null padding."""
    cfg, params = tiny_setup
    T, C = 11, 8
    toks = jax.random.randint(jax.random.PRNGKey(7), (T,), 0, 64)
    table = jnp.array([1, 2, 3], jnp.int32)  # exactly ceil(11/4) blocks

    kc, vc = _empty_cache(cfg)
    padded = jnp.concatenate([toks, jnp.zeros(12 - T, toks.dtype)])
    logits_full, kc_ref, _ = L.prefill(
        params, cfg, padded, jnp.int32(T), kc, vc, table
    )

    kc2, vc2 = _empty_cache(cfg)
    logits_chunk = None
    for start in range(0, T, C):
        chunk = toks[start : start + C]
        chunk = jnp.concatenate(
            [chunk, jnp.zeros(C - chunk.shape[0], toks.dtype)]
        )
        logits_chunk, kc2, vc2 = L.prefill_chunk(
            params, cfg, chunk, jnp.int32(start), jnp.int32(T),
            kc2, vc2, table,
        )
    np.testing.assert_allclose(
        np.asarray(logits_full), np.asarray(logits_chunk), atol=1e-2, rtol=1e-2
    )
    used = np.asarray(table)
    k_ref = np.stack(kc_ref)[:, :, used].astype(np.float32).reshape(-1, 12, cfg.head_dim)
    k_new = np.stack(kc2)[:, :, used].astype(np.float32).reshape(-1, 12, cfg.head_dim)
    np.testing.assert_allclose(k_ref[:, :T], k_new[:, :T], atol=1e-2, rtol=1e-2)


def test_mistral_sliding_window_serves_full_context():
    """Mistral-family configs declare sliding-window attention; the mask
    is implemented in the attention ops, so the model serves its FULL
    declared context (the r4 clamp is gone)."""
    cfg = L.LlamaConfig.from_hf_dict(
        {"model_type": "mistral", "hidden_size": 64,
         "num_attention_heads": 4, "max_position_embeddings": 32768,
         "sliding_window": 4096}
    )
    assert cfg.max_position_embeddings == 32768
    assert cfg.sliding_window == 4096
    assert cfg.layer_window(0) == 4096  # every layer slides (no pattern)
    # null / absent windows -> plain full attention
    cfg2 = L.LlamaConfig.from_hf_dict(
        {"model_type": "mistral", "max_position_embeddings": 32768,
         "sliding_window": None}
    )
    assert cfg2.sliding_window is None and cfg2.layer_window(0) is None
    # qwen2-style numeric window with use_sliding_window=false: disabled
    cfg3 = L.LlamaConfig.from_hf_dict(
        {"model_type": "qwen2", "max_position_embeddings": 32768,
         "sliding_window": 4096, "use_sliding_window": False}
    )
    assert cfg3.sliding_window is None


# --------------------------------------------------- idle lanes (PR 29)


@pytest.mark.parametrize("attn_impl", ["xla", "pallas_interpret"])
def test_idle_lanes_change_nothing_for_the_live(tiny_setup, attn_impl):
    """`decode_multi@H4` over [never admitted, A, B, never admitted], where
    B's limit ends it two steps into the horizon (its later writes go to
    slot 0, like an idle lane's): A and B read what they read as a batch of
    two, and A what it reads alone."""
    from dynamo_tpu.engine.jax_engine.model_runner import ModelRunner
    from dynamo_tpu.ops.sampling import MAX_EOS_IDS

    cfg, params = tiny_setup
    cfg = dataclasses.replace(cfg, attn_impl=attn_impl)
    kc, vc = _empty_cache(cfg)
    t_a = jax.random.randint(jax.random.PRNGKey(2), (7,), 0, 64)
    t_b = jax.random.randint(jax.random.PRNGKey(3), (5,), 0, 64)
    pad = lambda a: jnp.concatenate([a, jnp.zeros(8 - a.shape[0], a.dtype)])
    tab_a, tab_b = jnp.array([1, 2, 5], jnp.int32), jnp.array([3, 4], jnp.int32)
    _, kc, vc = L.prefill(params, cfg, pad(t_a), jnp.int32(7), kc, vc, tab_a[:2])
    _, kc, vc = L.prefill(params, cfg, pad(t_b), jnp.int32(5), kc, vc, tab_b)
    tables = np.zeros((4, 8), np.int32)  # a never-admitted lane: zeros
    tables[1, :3], tables[2, :2] = tab_a, tab_b
    tokens = np.array([0, 11, 22, 0], np.int32)
    positions = np.array([0, 7, 5, 0], np.int32)  # ... at position 0
    limit = np.array([0, 100, 2, 0], np.int32)
    active = np.array([False, True, True, False])
    H = 4

    def run(lanes):
        n = len(lanes)
        packed, _, _ = jax.jit(
            functools.partial(ModelRunner._decode_multi_impl, cfg, None, None, 4),
            static_argnums=(0,),
        )(
            H, params, kc, vc, jnp.asarray(tokens[lanes]),
            jnp.asarray(positions[lanes]), jnp.asarray(tables[lanes]),
            jnp.zeros((n, 2), jnp.uint32), jnp.zeros(n), jnp.ones(n),
            jnp.zeros(n, jnp.int32), jnp.ones(n, bool),
            jnp.asarray(active[lanes]),
            jnp.asarray(limit[lanes]), jnp.zeros(n, jnp.int32),
            jnp.full((n, MAX_EOS_IDS), -1, jnp.int32),
        )
        return np.asarray(packed)  # [H, n, 2 + 2K]

    full, pair, alone = run([0, 1, 2, 3]), run([1, 2]), run([1])
    assert (full[:, [0, 3], 0] == -1).all()  # idle lanes emit nothing
    assert (full[:2, 2, 0] >= 0).all() and (full[2:, 2, 0] == -1).all()
    K = (full.shape[-1] - 2) // 2
    ids = lambda rows: rows[..., [0] + list(range(2, 2 + K))]
    lps = lambda rows: rows[..., [1] + list(range(2 + K, 2 + 2 * K))]
    for got, want in [
        (full[:, 1], pair[:, 0]), (full[:, 1], alone[:, 0]),
        (full[:2, 2], pair[:2, 1]),
    ]:
        assert np.array_equal(ids(got), ids(want))
        np.testing.assert_allclose(lps(got), lps(want), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("family", ["llama", "mla_moe"])
def test_a_slot_in_the_null_block_is_a_context_of_zero(monkeypatch, family, tiny_setup):
    """One rule for both families: attention is handed a context of 0 for
    exactly the lanes whose write slot lies in the null block, whatever
    their position says."""
    bs = 4
    positions = jnp.array([0, 7, 9, 5], jnp.int32)
    slots = jnp.array([0, 1 * bs + 3, bs - 1, 4 * bs + 1], jnp.int32)
    tables = jnp.zeros((4, 8), jnp.int32).at[1, :2].set(jnp.array([1, 2]))
    tables = tables.at[3, :2].set(jnp.array([3, 4]))
    tokens = jnp.array([0, 11, 22, 33], jnp.int32)
    seen = []
    if family == "llama":
        cfg, params = tiny_setup
        kc, vc = _empty_cache(cfg, block_size=bs)
        real = L.decode_append_attention  # the append and the attention
        monkeypatch.setattr(
            L, "decode_append_attention",
            lambda q, k, v, kn, vn, sl, bt, ctx, **kw: (
                seen.append(np.asarray(ctx)),
                real(q, k, v, kn, vn, sl, bt, ctx, **kw),
            )[1],
        )
        with jax.disable_jit():  # the spy reads values: run each layer's body
            L.decode(params, cfg, tokens, positions, kc, vc, tables, slots)
        layers = cfg.num_layers
    else:
        from dynamo_tpu.models import mla_moe as M
        from tests.test_mla_moe import BS, planes, toy

        assert BS == bs
        cfg, params = toy()[:2]
        real = M.mla.decode_attention
        monkeypatch.setattr(
            M.mla, "decode_attention",
            lambda q, plane, bt, ctx, **kw: (
                seen.append(np.asarray(ctx)), real(q, plane, bt, ctx, **kw)
            )[1],
        )
        with jax.disable_jit():
            M.decode(params, cfg, tokens, positions, planes(cfg), (), tables, slots)
        layers = cfg.num_layers
    assert len(seen) == layers
    for ctx in seen:
        assert ctx.tolist() == [0, 8, 0, 6]
