"""A horizon through `ops.attention.decode_append_attention` (PR 47).

The decode step's cache append is done inside the paged decode kernel. Held
here at a toy size, in float32 with the kernel interpreted: a `decode_multi`
dispatch of four steps streams the tokens and leaves the caches that the tree
before left, for the dense family and for a hybrid one. "The tree before" is
the same program with the entry replaced by the pair it stands for, the row
scatter and then the kernel: the kernel reads from the page what the other
form puts into the page's tile, so the arithmetic is the same and the two
must agree to the last bit, everywhere but in the null block, which the pair
writes for an idle lane and the kernel does not.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.jax_engine.model_runner import ModelRunner
from dynamo_tpu.models import forms_called, hybrid_ssm, layer_bodies_called, llama
from dynamo_tpu.ops import attention as A
from dynamo_tpu.ops.sampling import MAX_EOS_IDS

H, BS, NB, MAX_BLOCKS = 4, 4, 48, 8
# lane -> position of the token it feeds: a page's last slot (so the horizon
# opens a new page), a page's first, idle, the middle of a page
POSITIONS = [7, 12, 0, 18]
ACTIVE = [True, True, False, True]


def the_pair(q, k_cache, v_cache, k_new, v_new, slot_indices, block_tables, context_lens, **kw):
    k_cache, v_cache = A.write_decode_kv(k_cache, v_cache, k_new, v_new, slot_indices)
    attn = A.paged_decode_attention(q, k_cache, v_cache, block_tables, context_lens, **kw)
    return attn, k_cache, v_cache


def dense():
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=64), attn_impl="pallas_interpret")
    params = llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    pages = lambda key: tuple(
        jax.random.normal(k, (cfg.num_kv_heads, NB, BS, cfg.head_dim), jnp.float32)
        for k in jax.random.split(jax.random.PRNGKey(key), cfg.num_layers)
    )
    return llama, cfg, params, pages(1), pages(2)


def hybrid():
    from tests.test_hybrid_ssm import LANES, caches, toy

    assert LANES + 1 == len(POSITIONS)  # its slot arrays: a row a lane
    cfg, params, *_ = toy("pallas_interpret")
    k_cache, v_cache = caches(cfg, fill=0.25)
    noise = lambda a, key: a + jax.random.normal(jax.random.PRNGKey(key), a.shape, a.dtype)
    return (
        hybrid_ssm, cfg, params,
        tuple(noise(a, i) for i, a in enumerate(k_cache)),
        tuple(noise(a, 100 + i) for i, a in enumerate(v_cache)),
    )


def horizon(cfg, params, k_cache, v_cache):
    B = len(POSITIONS)
    tables = np.arange(1, 1 + B * MAX_BLOCKS, dtype=np.int32).reshape(B, MAX_BLOCKS)
    tables[2] = 0  # the idle lane's
    with layer_bodies_called():
        packed, k_cache, v_cache = jax.jit(
            functools.partial(ModelRunner._decode_multi_impl, cfg, None, None, BS),
            static_argnums=(0,),
        )(
            H, params, k_cache, v_cache, jnp.asarray([5, 9, 0, 17], jnp.int32),
            jnp.asarray(POSITIONS, jnp.int32), jnp.asarray(tables),
            jnp.zeros((B, 2), jnp.uint32), jnp.zeros(B, jnp.float32),
            jnp.ones(B, jnp.float32), jnp.zeros(B, jnp.int32),
            jnp.ones(B, bool), jnp.asarray(ACTIVE), jnp.full(B, 100, jnp.int32),
            jnp.zeros(B, jnp.int32), jnp.full((B, MAX_EOS_IDS), -1, jnp.int32),
        )
    return np.asarray(packed), k_cache, v_cache, forms_called()


@pytest.mark.parametrize("family", [dense, hybrid])
def test_a_horizon_through_the_entry_is_the_tree_befores(family, monkeypatch):
    module, cfg, params, k_cache, v_cache = family()
    paged = [
        i for i, a in enumerate(k_cache) if a.shape[1:3] == (NB, BS)
    ]  # the attention layers: the others' arrays are a lane's state
    jax.clear_caches()  # a body's trace is kept for the process
    got, k_got, v_got, counted = horizon(cfg, params, k_cache, v_cache)
    # every attention layer appends in the kernel, counted once for H steps
    assert counted == {"kv_append_folded": len(paged)}

    monkeypatch.setattr(module, "decode_append_attention", the_pair)
    jax.clear_caches()
    want, k_want, v_want, counted = horizon(cfg, params, k_cache, v_cache)
    assert counted == {"kv_append_scattered": len(paged)}
    jax.clear_caches()

    live = np.asarray(ACTIVE)
    assert (got[:, live, 0] >= 0).all() and (got[:, ~live, 0] == -1).all()
    assert np.array_equal(got, want)  # tokens, log-probs, the top ids
    for have, ref, was in ((k_got, k_want, k_cache), (v_got, v_want, v_cache)):
        for i, (a, b, before) in enumerate(zip(have, ref, was)):
            a, b, before = np.asarray(a), np.asarray(b), np.asarray(before)
            if i not in paged:
                assert np.array_equal(a, b)
                continue
            assert np.array_equal(a[:, 1:], b[:, 1:])
            assert np.array_equal(a[:, 0], before[:, 0])  # the null block
            # each live lane wrote its H rows and nothing else changed
            assert ((a != before).any(axis=(0, 3))).sum() == H * live.sum()
