"""Pallas TPU kernel: decode-step attention over a paged *latent* cache.

Multi-head latent attention (DeepSeek-V2/V3's MLA) caches, per token and
layer, one row `[ckv | k_pe]` (the normed compressed latent, then the one
rope key all heads share) instead of keys and values by head. With the
key/value up-projection absorbed into the query and the output
(`q' = q_nope Wkvb_k^T`), a decode step is, for each lane,

    s[h, t] = (q'[h] . ckv[t] + q_pe[h] . k_pe[t]) * scale
    o'[h]   = sum_t softmax(s[h])[t] ckv[t]

that is one shared "key head" as wide as the row, scored against every query
head at once, whose values are the row's first `value_width` entries. No
per-head key or value is ever made from the cache.

  grid = (B,); the plane stays in HBM. Each grid step walks the lane's
  block table in chunks of W pages, DMA-gathering them into a double
  buffer (chunk c+1 in flight while chunk c computes) and folding each
  [W*bs, width] chunk into an online-softmax accumulator [Hq, value_width].
  The loop bound is ceil(ctx_len / (W*bs)): a short lane costs no bytes for
  pages it does not have, and a lane whose context is 0 (no request in it)
  costs nothing at all.

The structure is `pallas_attention._decode_kernel`'s (which see for the DMA
discipline) with one plane, no grouping and no window; it is a kernel of its
own because its operands are: that kernel's pages are Hkv strips of [bs, 128]
under [group, 128] query tiles, this one's rows are 576 wide, shared by heads.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _mla_decode_kernel(
    block_tables_ref,  # [B, max_blocks] int32 (SMEM)
    context_lens_ref,  # [B] int32 (SMEM)
    q_ref,  # [1, Hq, width] VMEM: [q' | q_pe] of this lane
    c_hbm,  # [num_blocks, block_size, width]: the layer's plane, in HBM
    o_ref,  # [1, Hq, value_width]
    c_buf,  # [2, W*block_size, width] VMEM
    sems,  # DMA semaphores [2, W]
    m_ref,  # [Hq, 128] f32 running max
    l_ref,  # [Hq, 128] f32 running sum
    acc_ref,  # [Hq, value_width] f32
    *,
    block_size: int,
    pages_per_chunk: int,
    value_width: int,
    scale: float,
):
    b = pl.program_id(0)
    ctx_len = context_lens_ref[b]
    W = pages_per_chunk
    chunk_tokens = W * block_size
    n_chunks = lax.div(ctx_len + chunk_tokens - 1, chunk_tokens)
    last_page = jnp.maximum((ctx_len - 1) // block_size, 0)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def dma(c, slot, i):
        # pages past the end clamp to the last valid page (masked below)
        page = block_tables_ref[b, jnp.minimum(c * W + i, last_page)]
        return pltpu.make_async_copy(
            c_hbm.at[page],
            c_buf.at[slot, pl.ds(i * block_size, block_size), :],
            sems.at[slot, i],
        )

    def issue(c, slot):
        for i in range(W):
            dma(c, slot, i).start()

    @pl.when(n_chunks > 0)
    def _go():
        issue(0, 0)

        def loop_body(c, _):
            slot = c % 2

            @pl.when(c + 1 < n_chunks)
            def _prefetch():
                issue(c + 1, (c + 1) % 2)

            for i in range(W):
                dma(c, slot, i).wait()

            q = q_ref[0]  # [Hq, width]
            rows = c_buf[slot]  # [W*bs, width]
            s = lax.dot_general(
                q, rows, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [Hq, W*bs]
            pos = c * chunk_tokens + lax.broadcasted_iota(
                jnp.int32, s.shape, dimension=1
            )
            s = jnp.where(pos < ctx_len, s, NEG_INF)
            m_prev = m_ref[:, :1]
            l_prev = l_ref[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + lax.dot_general(
                p.astype(rows.dtype), rows[:, :value_width],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
            return 0

        lax.fori_loop(0, n_chunks, loop_body, 0)

    l = l_ref[:, :1]
    safe_l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


def mla_paged_decode_pallas(
    q: jax.Array,  # [B, Hq, width]: absorbed query, then the rope query
    plane: jax.Array,  # [num_blocks, block_size, width]
    block_tables: jax.Array,  # [B, max_blocks] int32
    context_lens: jax.Array,  # [B] int32, the new token included; 0 = idle
    *,
    value_width: int,
    scale: float,
    pages_per_chunk: int = 16,
    interpret: bool = False,
) -> jax.Array:
    """o' [B, Hq, value_width]."""
    B, Hq, width = q.shape
    _, block_size, _ = plane.shape
    W = max(1, min(pages_per_chunk, block_tables.shape[1]))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hq, width), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, Hq, value_width), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, W * block_size, width), plane.dtype),
            pltpu.SemaphoreType.DMA((2, W)),
            pltpu.VMEM((Hq, 128), jnp.float32),
            pltpu.VMEM((Hq, 128), jnp.float32),
            pltpu.VMEM((Hq, value_width), jnp.float32),
        ],
    )
    kernel = pl.pallas_call(
        functools.partial(
            _mla_decode_kernel,
            block_size=block_size,
            pages_per_chunk=W,
            value_width=value_width,
            scale=float(scale),
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, value_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )
    return kernel(
        block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
        q.astype(plane.dtype), plane,
    )
