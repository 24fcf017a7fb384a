"""The cache walk every step program shares writes what a call names, there
and nowhere else (PR 26: one cache array per layer, row scatter).

Every layer's cache starts as a sentinel, with random keys and values in
the blocks a call reads as context. Each program then runs on the runner's
container (a tuple of per-layer [Hkv, nb, bs, D] arrays) and is held to a
dense reference written here: one [L, Hkv, nb, bs, D] array walked with
`cache[i]` and written by plain indexing, as the tree before PR 26 did. The
reference shares the layer arithmetic and the attention ops (neither is
what this PR changes) and differs in everything this PR touches: the
container, the walk and the form of the writes. Held bit for bit: slots a
call does not name keep their sentinel in every layer, and written slots
and logits equal the reference's.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.jax_engine.model_runner import ModelRunner
from dynamo_tpu.models import llama as L
from dynamo_tpu.ops import attention as A
from dynamo_tpu.ops.sampling import MAX_EOS_IDS, sample_tokens_full

BS, NB, MAX_BLOCKS = 4, 24, 5  # block size, pool blocks, table width
SENTINEL = (7.0, -3.0)  # K, V; layer i adds i


@pytest.fixture(scope="module", params=["xla", "pallas_interpret"])
def model(request):
    cfg = dataclasses.replace(
        L.LlamaConfig.tiny(vocab_size=64), attn_impl=request.param
    )
    params = L.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


# ------------------------------------------------------------ the two caches


def dense_cache(cfg, context_blocks, seed=1):
    """(k, v) as one [L, Hkv, nb, bs, D] bf16 array each: the sentinel
    everywhere, random context in `context_blocks`."""
    rng = np.random.default_rng(seed)
    shape = (cfg.num_layers, cfg.num_kv_heads, NB, BS, cfg.head_dim)
    out = []
    for base in SENTINEL:
        a = np.broadcast_to(
            base + np.arange(cfg.num_layers, dtype=np.float32)[:, None, None, None, None],
            shape,
        ).copy()
        blocks = sorted(set(np.asarray(context_blocks).reshape(-1).tolist()))
        a[:, :, blocks] = rng.normal(size=a[:, :, blocks].shape)
        out.append(jnp.asarray(a, jnp.bfloat16))
    return tuple(out)


def per_layer(dense):
    """The runner's container from a dense array."""
    return tuple(dense[i] for i in range(dense.shape[0]))


def assert_cache_equal(got: tuple, want, before, named_slots):
    """got: per-layer tuple; want, before: dense arrays. Everything equals
    the reference, and outside `named_slots` (flat slot ids) nothing moved."""
    got = np.stack([np.asarray(c.astype(jnp.float32)) for c in got])
    want = np.asarray(want.astype(jnp.float32))
    before = np.asarray(before.astype(jnp.float32))
    np.testing.assert_array_equal(got, want)
    Lh = got.shape[:2]
    flat = lambda a: a.reshape(*Lh, NB * BS, -1)
    untouched = np.ones(NB * BS, bool)
    untouched[np.asarray(sorted(named_slots), np.int64)] = False
    np.testing.assert_array_equal(
        flat(got)[:, :, untouched], flat(before)[:, :, untouched]
    )
    # and what was named was written: no sentinel, no old context, is left
    assert not np.any(
        np.all(flat(got)[:, :, ~untouched] == flat(before)[:, :, ~untouched], axis=-1)
    )


# ------------------------------------------------------- the dense reference


def write_tokens(cache, i, new, slots):
    """new [T, Hkv, D] into layer i's flat slots, by block and offset."""
    layer = cache[i].at[:, slots // BS, slots % BS].set(
        new.transpose(1, 0, 2).astype(cache.dtype)
    )
    return cache.at[i].set(layer)


def write_blocks(cache, i, new, blocks):
    """new [n*bs, Hkv, D] into layer i's blocks, one block at a time."""
    layer = cache[i]
    for j in range(new.shape[0] // BS):
        layer = layer.at[:, blocks[j]].set(
            new[j * BS:(j + 1) * BS].transpose(1, 0, 2).astype(cache.dtype)
        )
    return cache.at[i].set(layer)


def dense_walk(params, cfg, tokens, positions, k_cache, v_cache, write, attend):
    """The model over one dense array per K and V; returns the last hidden
    states and the arrays."""
    freqs = L._rope_pair(cfg)
    x = L._embed(params, cfg, tokens)
    for i, layer in enumerate(params["layers"]):
        q, k, v = L._qkv(x, layer, cfg, L._layer_freqs(cfg, i, freqs), positions)
        k_cache = write(k_cache, i, k)
        v_cache = write(v_cache, i, v)
        attn = attend(q, k_cache[i], v_cache[i], k, v)
        x = L._attn_out(attn.reshape(q.shape), x, layer, cfg)
        x = L._mlp(x, layer, cfg, None)
    return x, k_cache, v_cache


def ref_decode(params, cfg, tokens, positions, k, v, tables, slots):
    x, k, v = dense_walk(
        params, cfg, tokens, positions, k, v,
        lambda c, i, new: write_tokens(c, i, new, slots),
        lambda q, kc, vc, *_: A.paged_decode_attention(
            q, kc, vc, tables, positions + 1, impl=cfg.attn_impl,
            scale=cfg.attn_scale,
        ),
    )
    return L._logits(x, params, cfg), k, v


def ref_verify(params, cfg, tokens, positions, k, v, tables, slots):
    B, S = tokens.shape
    x, k, v = dense_walk(
        params, cfg, tokens.reshape(-1), positions.reshape(-1), k, v,
        lambda c, i, new: write_tokens(c, i, new, slots.reshape(-1)),
        lambda q, kc, vc, *_: A.paged_verify_attention(
            q.reshape(B, S, cfg.num_heads, cfg.head_dim), kc, vc, tables,
            positions, scale=cfg.attn_scale, impl=cfg.attn_impl,
        ),
    )
    return L._logits(x, params, cfg).reshape(B, S, -1), k, v


def ref_packed(params, cfg, tokens, positions, segments, slots, k, v, last):
    x, k, v = dense_walk(
        params, cfg, tokens, positions, k, v,
        lambda c, i, new: write_tokens(c, i, new, slots),
        lambda q, kc, vc, kn, vn: A.packed_prefill_attention(
            q, kn, vn, segments, scale=cfg.attn_scale
        ),
    )
    return L._logits(x[last], params, cfg), k, v


def ref_chunk(params, cfg, tokens, start, valid, k, v, table):
    C = tokens.shape[0]
    blocks = np.concatenate([np.asarray(table), np.zeros(C // BS, np.int32)])[
        int(start) // BS:int(start) // BS + C // BS
    ]
    x, k, v = dense_walk(
        params, cfg, tokens, start + jnp.arange(C, dtype=jnp.int32), k, v,
        lambda c, i, new: write_blocks(c, i, new, blocks),
        lambda q, kc, vc, *_: A.chunked_prefill_attention(
            q, kc, vc, table, start, scale=cfg.attn_scale
        ),
    )
    idx = jnp.clip(valid - 1 - start, 0, C - 1)
    return L._logits(x[idx][None, :], params, cfg)[0], k, v


# ------------------------------------------------------------------ a batch


def batch(B=3):
    """B lanes with scattered tables and contexts of unlike length; lane
    B-1 has written nothing yet and starts a block."""
    tables = np.zeros((B, MAX_BLOCKS), np.int32)
    ids = np.array([17, 3, 9, 21, 5, 12, 2, 19, 8, 14, 23, 6, 11, 1, 20])
    for lane in range(B):
        tables[lane] = ids[lane * MAX_BLOCKS:(lane + 1) * MAX_BLOCKS]
    positions = np.array([9, 6, 0, 13][:B], np.int32)
    return tables, positions


def slots_of(tables, positions):
    lanes = np.arange(tables.shape[0])
    if positions.ndim == 2:
        lanes = lanes[:, None]
    return (tables[lanes, positions // BS] * BS + positions % BS).astype(np.int32)


def bits(a):
    return np.asarray(a, np.float32)


# ---------------------------------------------------------------- the tests


def test_decode_writes_its_slots_only(model):
    cfg, params = model
    tables, positions = batch()
    slots = slots_of(tables, positions)
    k0, v0 = dense_cache(cfg, tables)
    tokens = jnp.asarray([5, 9, 11], jnp.int32)
    args = (jnp.asarray(positions),)
    want, kw, vw = jax.jit(functools.partial(ref_decode, params, cfg))(
        tokens, *args, k0, v0, jnp.asarray(tables), jnp.asarray(slots)
    )
    got, kg, vg = jax.jit(functools.partial(L.decode, params, cfg))(
        tokens, *args, per_layer(k0), per_layer(v0), jnp.asarray(tables),
        jnp.asarray(slots),
    )
    np.testing.assert_array_equal(bits(got), bits(want))
    assert_cache_equal(kg, kw, k0, slots)
    assert_cache_equal(vg, vw, v0, slots)


def test_decode_verify_writes_its_slots_only(model):
    cfg, params = model
    tables, first = batch()
    S = 3
    positions = first[:, None] + np.arange(S, dtype=np.int32)[None, :]
    slots = slots_of(tables, positions)
    k0, v0 = dense_cache(cfg, tables)
    tokens = jnp.asarray([[5, 9, 11], [7, 8, 3], [2, 4, 6]], jnp.int32)
    rest = (jnp.asarray(tables), jnp.asarray(slots))
    want, kw, vw = jax.jit(functools.partial(ref_verify, params, cfg))(
        tokens, jnp.asarray(positions), k0, v0, *rest
    )
    got, kg, vg = jax.jit(functools.partial(L.decode_verify, params, cfg))(
        tokens, jnp.asarray(positions), per_layer(k0), per_layer(v0), *rest
    )
    np.testing.assert_array_equal(bits(got), bits(want))
    assert_cache_equal(kg, kw, k0, slots.reshape(-1))
    assert_cache_equal(vg, vw, v0, slots.reshape(-1))


def test_prefill_packed_writes_its_slots_only(model):
    cfg, params = model
    tables, _ = batch()
    lens, P = (6, 9), 16  # two prompts in one 16-token program, one pad
    tokens = np.zeros(P, np.int32)
    positions = np.zeros(P, np.int32)
    segments = np.full(P, -1, np.int32)
    slots = np.zeros(P, np.int32)  # the pad lane writes the null slot 0
    at = 0
    for seg, n in enumerate(lens):
        pos = np.arange(n, dtype=np.int32)
        tokens[at:at + n] = 3 + (7 * pos + 11 * seg) % 50
        positions[at:at + n] = pos
        segments[at:at + n] = seg
        slots[at:at + n] = tables[seg, pos // BS] * BS + pos % BS
        at += n
    last = jnp.asarray([lens[0] - 1, lens[0] + lens[1] - 1, 0], jnp.int32)
    k0, v0 = dense_cache(cfg, [])
    head = tuple(jnp.asarray(a) for a in (tokens, positions, segments, slots))
    want, kw, vw = jax.jit(functools.partial(ref_packed, params, cfg))(
        *head, k0, v0, last
    )
    got, kg, vg = jax.jit(functools.partial(L.prefill_packed, params, cfg))(
        *head, per_layer(k0), per_layer(v0), last
    )
    np.testing.assert_array_equal(bits(got), bits(want))
    assert_cache_equal(kg, kw, k0, slots)
    assert_cache_equal(vg, vw, v0, slots)


@pytest.mark.parametrize("start", [0, 8])
def test_prefill_chunk_writes_its_blocks_only(model, start):
    cfg, params = model
    tables, _ = batch()
    table, C, valid = tables[0], 8, 14  # the second chunk's tail is padding
    blocks = table[start // BS:start // BS + C // BS]
    named = (blocks[:, None] * BS + np.arange(BS)[None, :]).reshape(-1)
    k0, v0 = dense_cache(cfg, table[:start // BS])
    tokens = jnp.asarray(3 + (5 * np.arange(C)) % 50, jnp.int32)
    head = (tokens, jnp.int32(start), jnp.int32(valid))
    want, kw, vw = jax.jit(
        lambda t, s, n, k, v: ref_chunk(params, cfg, t, start, n, k, v, table)
    )(*head, k0, v0)
    got, kg, vg = jax.jit(functools.partial(L.prefill_chunk, params, cfg))(
        *head, per_layer(k0), per_layer(v0), jnp.asarray(table)
    )
    np.testing.assert_array_equal(bits(got), bits(want))
    assert_cache_equal(kg, kw, k0, named)
    assert_cache_equal(vg, vw, v0, named)


def greedy(B):
    return dict(
        keys=jnp.zeros((B, 2), jnp.uint32), temps=jnp.zeros(B, jnp.float32),
        top_ps=jnp.ones(B, jnp.float32), top_ks=jnp.zeros(B, jnp.int32),
        want=jnp.ones(B, bool),
        eos_ids=jnp.full((B, MAX_EOS_IDS), -1, jnp.int32),
    )


def pack(sample):
    tok, lp, top_ids, top_lps = sample
    return np.concatenate(
        [bits(tok)[:, None], bits(lp)[:, None], bits(top_ids), bits(top_lps)],
        axis=-1,
    )


def test_decode_multi_writes_its_slots_only(model):
    """decode_multi@H3: each step's token feeds the next on the device."""
    cfg, params = model
    H, B = 3, 3
    tables, positions = batch()
    g = greedy(B)
    k0, v0 = dense_cache(cfg, tables)
    tokens = jnp.asarray([5, 9, 11], jnp.int32)
    packed, kg, vg = jax.jit(
        functools.partial(ModelRunner._decode_multi_impl, cfg, None, None, BS),
        static_argnums=(0,),
    )(
        H, params, per_layer(k0), per_layer(v0), tokens,
        jnp.asarray(positions), jnp.asarray(tables), g["keys"], g["temps"],
        g["top_ps"], g["top_ks"], g["want"], jnp.ones(B, bool),
        jnp.full(B, 100, jnp.int32), jnp.zeros(B, jnp.int32), g["eos_ids"],
    )
    step = jax.jit(functools.partial(ref_decode, params, cfg))
    kw, vw, named = k0, v0, []
    for h in range(H):
        slots = slots_of(tables, positions + h)
        named += slots.tolist()
        logits, kw, vw = step(
            tokens, jnp.asarray(positions + h), kw, vw, jnp.asarray(tables),
            jnp.asarray(slots),
        )
        sample = sample_tokens_full(
            logits, None, g["temps"], g["top_ps"], g["top_ks"], g["want"],
            keys=g["keys"].at[:, 1].add(jnp.uint32(h)),
        )
        np.testing.assert_array_equal(bits(packed[h]), pack(sample))
        tokens = sample[0]
    assert_cache_equal(kg, kw, k0, named)
    assert_cache_equal(vg, vw, v0, named)


def test_mixed_step_writes_its_slots_and_blocks_only(model):
    """mixed_step@c1: one prefill chunk, then the decode batch."""
    cfg, params = model
    B = 2
    tables, positions = batch(3)
    chunk_table, C, valid = tables[2], 8, 7
    tables, positions = tables[:B], positions[:B]
    slots = slots_of(tables, positions)
    g = greedy(B)
    k0, v0 = dense_cache(cfg, tables)
    c_tokens = jnp.asarray(3 + (5 * np.arange(C)) % 50, jnp.int32)
    chunk = (
        c_tokens, jnp.int32(0), jnp.int32(valid), jnp.asarray(chunk_table),
        jnp.zeros(2, jnp.uint32), jnp.float32(0.0), jnp.float32(1.0),
        jnp.int32(0), jnp.bool_(True), jnp.float32(1.0),
        jnp.full(MAX_EOS_IDS, -1, jnp.int32),
        jnp.bool_(False),
    )
    tokens = jnp.asarray([5, 9], jnp.int32)
    outs, kg, vg = jax.jit(
        functools.partial(ModelRunner._mixed_impl, cfg, None, None)
    )(
        params, per_layer(k0), per_layer(v0), (chunk,), tokens,
        jnp.asarray(positions), jnp.asarray(tables), jnp.asarray(slots),
        g["keys"], g["temps"], g["top_ps"], g["top_ks"], g["want"],
        g["eos_ids"],
        jnp.zeros(B, bool),
    )
    def ref_mixed(k, v):
        c_logits, k, v = ref_chunk(
            params, cfg, c_tokens, 0, jnp.int32(valid), k, v, chunk_table
        )
        d_logits, k, v = ref_decode(
            params, cfg, tokens, jnp.asarray(positions), k, v,
            jnp.asarray(tables), jnp.asarray(slots),
        )
        c_out = ModelRunner._sample_one(
            c_logits, c_tokens, jnp.int32(valid), *chunk[4:]
        )
        d_out = sample_tokens_full(
            d_logits, None, g["temps"], g["top_ps"], g["top_ks"], g["want"],
            keys=g["keys"],
        )
        return tuple(c_out) + tuple(d_out), k, v

    # The program returns samples, not logits: token ids are held exactly,
    # log-probs to a few ulp (this composite compiles its soft-max apart
    # from the reference's; the programs that return logits, above, are
    # held bit for bit, and so are the caches here).
    wants, kw, vw = jax.jit(ref_mixed)(k0, v0)
    for got, want in zip(outs, wants):
        if jnp.issubdtype(got.dtype, jnp.integer):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        else:
            np.testing.assert_allclose(bits(got), bits(want), rtol=2e-6, atol=2e-6)
    named = slots.tolist() + (
        chunk_table[:C // BS, None] * BS + np.arange(BS)[None, :]
    ).reshape(-1).tolist()
    assert_cache_equal(kg, kw, k0, named)
    assert_cache_equal(vg, vw, v0, named)


# ------------------------------------------------------------ the wire shape


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_extract_inject_round_trip_on_the_wire_shape(kv_dtype):
    """extract_blocks* / inject_blocks* keep [L, Hkv, n, bs, D] on the wire
    whatever the container on the device."""
    cfg = L.LlamaConfig.tiny(vocab_size=64)
    params = L.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)

    def runner():
        return ModelRunner(
            cfg, params, num_blocks=NB, block_size=BS, max_batch=2,
            max_model_len=MAX_BLOCKS * BS, kv_dtype=kv_dtype, attn_impl="xla",
        )

    src, dst = runner(), runner()
    assert len(src.k_cache) == cfg.num_layers
    prompt = list(range(3, 3 + 3 * BS))
    src.fetch_sample(src.prefill(prompt, [4, 9, 2], 0.0, 1.0, 0))
    wire = (cfg.num_layers, cfg.num_kv_heads, 3, BS, cfg.head_dim)
    k, v = src.extract_blocks([4, 9, 2])
    assert k.shape == wire and v.shape == wire and np.any(np.asarray(k, np.float32))
    before = [np.asarray(a) for a in jax.tree_util.tree_leaves(dst.k_cache)]
    if kv_dtype == "int8":
        kq, ks, vq, vs = src.extract_blocks_quant([4, 9, 2])
        assert kq.shape == wire and ks.shape == wire[:3] and kq.dtype == np.int8
        dst.inject_blocks_quant([7, 1, 5], kq, ks, vq, vs)
        back = dst.extract_blocks_quant([7, 1, 5])
        for got, want in zip(back, (kq, ks, vq, vs)):
            np.testing.assert_array_equal(got, want)
    else:
        dst.inject_blocks([7, 1, 5], k, v)
    k2, v2 = dst.extract_blocks([7, 1, 5])
    np.testing.assert_array_equal(np.asarray(k2, np.float32), np.asarray(k, np.float32))
    np.testing.assert_array_equal(np.asarray(v2, np.float32), np.asarray(v, np.float32))
    # nothing but the three blocks (and the padding's null block 0) moved
    after = [np.asarray(a) for a in jax.tree_util.tree_leaves(dst.k_cache)]
    others = [b for b in range(NB) if b not in (0, 7, 1, 5)]
    for a, b in zip(after, before):
        np.testing.assert_array_equal(a[:, others], b[:, others])
