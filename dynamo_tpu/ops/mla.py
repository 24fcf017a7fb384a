"""Multi-head latent attention over a paged latent cache.

What is cached, per token and layer, is one row `[ckv | k_pe | 0...]`: the
normed 512-wide latent, the 64-wide rope key that all heads share, and zeros
up to the next multiple of 128 lanes (the width a TPU DMA tile needs; the
model's `cache_kind` says both numbers). A layer's plane is
`[1, num_blocks, block_size, stored_width]`: the leading 1 is the "head" axis
of the grouped-query layout, so the row scatter and the block tables of
`ops/kv_quant.py` and `ops/attention.py` serve it unchanged.

Two forms of the same attention, the same numbers up to rounding:

* per head (`packed_attention`): keys `[k_nope | k_pe]` and values are made
  from the latent by the up-projection and scored head by head. Used where a
  program holds the whole prompt and nothing earlier is in the cache: packed
  prefill and the bucketed whole-prompt prefill.
* absorbed (`chunk_attention`, `decode_attention`): the up-projection is
  folded into the query and the output, and the cached rows are scored as
  they lie. Used by every program that reads the cache: decode, the decode
  horizon, prefill chunks and so mixed steps.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from dynamo_tpu.ops.attention import get_attention_impl

NEG_INF = -1e30


def rope_interleaved(x: jax.Array, positions: jax.Array, inv_freqs: jax.Array) -> jax.Array:
    """Rotate adjacent pairs (2i, 2i+1) of the last axis by
    `position * inv_freqs[i]`. x [T, ..., R]; positions [T]."""
    shape = x.shape
    ang = positions.astype(jnp.float32)[:, None] * inv_freqs  # [T, R/2]
    ang = ang.reshape((shape[0],) + (1,) * (x.ndim - 2) + (shape[-1] // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.astype(jnp.float32).reshape(shape[:-1] + (shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(shape).astype(x.dtype)


def packed_attention(
    q: jax.Array,  # [P, Hq, Dqk]
    k: jax.Array,  # [P, Hq, Dqk]
    v: jax.Array,  # [P, Hq, Dv]
    segment_ids: jax.Array,  # [P] int32; -1 marks padding
    scale: float,
) -> jax.Array:
    """Causal attention within each segment of a packed buffer, per head,
    with value heads narrower than key heads. Returns [P, Hq, Dv]."""
    P = q.shape[0]
    scores = jnp.einsum(
        "qhd,khd->hqk", q, k, preferred_element_type=jnp.float32
    ) * jnp.float32(scale)
    pos = jnp.arange(P)
    mask = (pos[None, :] <= pos[:, None]) & (
        segment_ids[None, :] == segment_ids[:, None]
    )
    scores = jnp.where(mask[None], scores, NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "hqk,khd->qhd", weights.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)


def chunk_attention(
    q: jax.Array,  # [C, Hq, W]: absorbed query, rope query, zeros
    plane: jax.Array,  # [1, num_blocks, block_size, W]
    block_table: jax.Array,  # [max_nb] int32: the whole prompt's blocks
    chunk_start: jax.Array,  # scalar int32: position of q[0]
    *,
    value_width: int,
    scale: float,
    key_block: int = 1024,
) -> jax.Array:
    """Absorbed attention of one prompt chunk over everything the cache
    holds of its sequence, the chunk itself included (written first).
    Walks the keys in blocks of `key_block` tokens with a running softmax
    and stops after the block that holds the chunk's last token, so scores
    are never `[Hq, C, context]` at once and a short context costs a short
    walk. Returns o' [C, Hq, value_width]."""
    C, Hq, W = q.shape
    bs = plane.shape[2]
    pages = max(1, min(key_block // bs, block_table.shape[0]))
    KB = pages * bs
    n_pages = block_table.shape[0]
    pad = (-n_pages) % pages
    table = jnp.concatenate([block_table, jnp.zeros(pad, block_table.dtype)])
    qpos = chunk_start + jnp.arange(C)
    n_blocks = (chunk_start + C + KB - 1) // KB
    sc = jnp.float32(scale)

    def body(j, carry):
        m, l, acc = carry
        ids = lax.dynamic_slice(table, (j * pages,), (pages,))
        rows = plane[0, ids].reshape(KB, W)
        s = jnp.einsum(
            "chw,kw->hck", q, rows, preferred_element_type=jnp.float32
        ) * sc
        kpos = j * KB + jnp.arange(KB)
        s = jnp.where((kpos[None, :] <= qpos[:, None])[None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "hck,kv->hcv", p.astype(rows.dtype), rows[:, :value_width],
            preferred_element_type=jnp.float32,
        )
        return m_new, l, acc

    init = (
        jnp.full((Hq, C), NEG_INF, jnp.float32),
        jnp.zeros((Hq, C), jnp.float32),
        jnp.zeros((Hq, C, value_width), jnp.float32),
    )
    _, l, acc = lax.fori_loop(0, n_blocks, body, init)
    out = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
    return out.transpose(1, 0, 2).astype(q.dtype)


def decode_attention(
    q: jax.Array,  # [B, Hq, W]
    plane: jax.Array,  # [1, num_blocks, block_size, W]
    block_tables: jax.Array,  # [B, max_blocks] int32
    context_lens: jax.Array,  # [B] int32, the new token included; 0 = idle
    *,
    value_width: int,
    scale: float,
    impl: Optional[str] = None,
) -> jax.Array:
    """Absorbed decode-step attention: o' [B, Hq, value_width]. On a TPU the
    Pallas kernel of `ops/pallas_mla.py` streams each lane's pages; the XLA
    form gathers every lane's whole table and is for CPU tests."""
    impl = get_attention_impl(impl)
    if impl != "xla":
        from dynamo_tpu.ops.pallas_mla import mla_paged_decode_pallas

        return mla_paged_decode_pallas(
            q, plane[0], block_tables, context_lens,
            value_width=value_width, scale=scale,
            interpret=impl == "pallas_interpret",
        )
    B, Hq, W = q.shape
    S = block_tables.shape[1] * plane.shape[2]
    rows = plane[0, block_tables].reshape(B, S, W)
    s = jnp.einsum(
        "bhw,bsw->bhs", q, rows, preferred_element_type=jnp.float32
    ) * jnp.float32(scale)
    mask = jnp.arange(S)[None, :] < context_lens[:, None]
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    # an idle lane (context 0) has no key: give it zeros, as the kernel does
    p = jnp.where(mask[:, None, :], jax.nn.softmax(s, axis=-1), 0.0)
    out = jnp.einsum(
        "bhs,bsv->bhv", p.astype(rows.dtype), rows[..., :value_width],
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)
