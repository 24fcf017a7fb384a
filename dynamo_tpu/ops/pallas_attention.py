"""Pallas TPU kernels for paged attention.

The hot op of the serving engine: decode-step attention over the paged KV
cache. The XLA reference path (ops/attention.py) gathers every sequence's
blocks into a dense [B, S, Hkv, D] window each step — O(B*S) HBM traffic
even for short sequences, plus a materialized gather. This kernel instead
streams exactly the blocks named by each sequence's block table:

  decode: grid = (B,), one cell a lane; the cache stays in HBM
  (memory_space=ANY). A cell runs a dynamic-length fori_loop over chunks
  of W pages, manually DMA-gathering the pages named by the
  scalar-prefetched block table into double-buffered VMEM scratch (chunk
  c+1's copies are in flight while chunk c computes) and folding each
  chunk into an online-softmax (flash) accumulator. A page is fetched for
  every KV head at once: the cache is head-major [Hkv, pages, bs, D]
  (block manager, transfer, tiers and tp sharding rest on that layout), so
  the copy is `k_hbm.at[:, page]`, Hkv strips of one [bs, D] tile under one
  descriptor, into [Hkv, W*bs, D] scratch, and scores and values are
  products batched over the head axis. The loop bound is
  ceil(ctx_len / W*bs), so a short sequence costs neither FLOPs nor HBM
  bandwidth for its unused pages, and a lane whose context is 0 (no request
  in it: `ops.attention.live_decode_lanes`) costs a scalar test and a row
  of zeros (PERF.md section 6, PR 29: what a grid of lanes x KV heads and
  idle lanes at a context of 1 cost).

  decode with the append folded in (`k_new`/`v_new` given): the step's new
  key and value rows reach the cache inside this call and not by a scatter
  program before it. A live lane's last page is always in the last chunk
  it fetches, so the cell puts the new row into that page's strip in VMEM
  (an iota compare on the sublane index) before the products, attends over
  it, and copies the strip back to the page in HBM from staging rows of
  its own; the caches come back as outputs aliased onto their inputs. The
  write is waited for two lanes later (or in the last cell), never by the
  lane that started it. An idle lane writes nothing. The staging rows and
  their flags live across cells, so this form's grid is "arbitrary".

  verify: the same walk with grid = (B, Hkv), one cell a lane and KV head,
  each (head, page) one contiguous tile.

All three programs (decode, prefill, verify) carry the full attention
feature set of the model zoo, applied INSIDE the online softmax:

  * sliding window (Mistral / Gemma2/3 local layers): chunk/block ranges
    wholly left of `[i - window + 1, i]` are never DMA'd — the chunk loop
    STARTS at the window's first chunk, so SWA decode reads O(window) KV
    bytes per step instead of O(context);
  * custom score scale (Gemma2/3 query_pre_attn_scalar);
  * logit softcap (Gemma2): `cap * tanh(s / cap)` applied to the scaled
    scores before the running max/sum update, matching the XLA reference
    bit-for-bit in f32.

GQA: q for one kv head is the [G, D] group slice; scores are a [G, W*bs]
matmul per chunk and head. The decode kernel sends keys to the MXU as the
bfloat16 they are stored as (every product of two bfloat16 numbers is exact
in the float32 accumulator); probabilities stay float32 into the value
product.

Int8-resident caches (DYN_KV_DTYPE=int8, ops/kv_quant.py): the decode and
verify kernels take optional per-(head, page) scale planes as extra
scalar-prefetch operands; pages are DMA'd as int8 (half the HBM traffic)
and the scale is multiplied onto the f32 VMEM tile inside the
online-softmax loop — dequantized K/V exists only in VMEM, never in HBM.
Note the int8 VMEM tile is (32, 128), so real-TPU int8 paging needs
block_size % 32 == 0 (guarded in ops/attention._pallas_tileable).

Replaces what the reference leaves to vLLM's CUDA paged_attention kernels
(vLLM is engine-delegated at lib/llm/src/engines.rs; see also the CUDA
block-copy kernel lib/llm/src/kernels/block_copy.cu for the layout-aware
precedent). Runs in interpret mode on CPU for tests.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.basics import run_kernel

NEG_INF = -1e30

# W: pages a decode lane fetches and folds at a time. Read on the chip at
# Mistral-7B's and Qwen2.5-7B's head counts (PERF.md section 6, PR 29): 16
# is a tenth to a quarter faster at 456-token lanes, 3% slower at 548 and a
# tenth slower on int8 pages, and pads a short lane further.
DECODE_PAGES_PER_CHUNK = 8


def _page_scale_row(vals: list, block_size: int) -> jax.Array:
    """[1, W*block_size] f32 row holding vals[i] over page i's columns.
    Built from a lane iota and selects: Mosaic cannot lower the
    [W, bs] -> [W*bs, 1] reshape a `jnp.repeat(...)[:, None]` needs."""
    col = jax.lax.broadcasted_iota(
        jnp.int32, (1, len(vals) * block_size), dimension=1
    )
    row = jnp.full(col.shape, vals[0], jnp.float32)
    for i in range(1, len(vals)):
        row = jnp.where(col >= i * block_size, vals[i], row)
    return row


def _apply_softcap(s: jax.Array, softcap: Optional[float]) -> jax.Array:
    """Gemma-2 logit soft-capping on the scaled scores (static no-op when
    the model doesn't use it, so non-Gemma programs compile unchanged)."""
    if softcap is None:
        return s
    return softcap * jnp.tanh(s / softcap)


def decode_kv_chunks_read(
    ctx_len: int,
    *,
    block_size: int,
    pages_per_chunk: int = DECODE_PAGES_PER_CHUNK,
    window: Optional[int] = None,
) -> int:
    """Number of KV chunks the decode kernel DMAs for one sequence — the
    same arithmetic the kernel runs, exported so benches/tests can assert
    the O(window) traffic claim without a hardware counter. Each chunk is
    `pages_per_chunk * block_size` tokens of K plus the same of V."""
    chunk_tokens = pages_per_chunk * block_size
    n_chunks = -(-ctx_len // chunk_tokens)
    kv_start = 0 if window is None else max(ctx_len - window, 0)
    return max(n_chunks - kv_start // chunk_tokens, 0)


def _decode_kernel(
    # scalar prefetch
    block_tables_ref,  # [B, max_blocks] int32 (SMEM)
    context_lens_ref,  # [B] int32 (SMEM); 0 = no request in the lane
    # int8-resident mode only (quantized=True): two extra scalar-prefetch
    # scale planes [Hkv, num_blocks] f32 ride SMEM, then the same refs
    *refs,
    # inputs (in *refs):
    # q_ref   [1, Hkv, G, D] VMEM — this lane's queries, every head
    # k_hbm   [Hkv, num_blocks, block_size, D] — full cache, stays in HBM
    #         (int8 mantissas in quantized mode — bf16 pages never touch
    #         HBM; dequant happens on the VMEM tile inside this loop)
    # v_hbm
    # kn_ref  [lanes_per_block, Hkv * D] VMEM — append only: the new key
    #         rows of this lane's block of lanes, a lane a sublane
    # vn_ref
    # o_ref   [1, Hkv, G, D] blocked output
    # k_out   append only: the caches again, aliased onto k_hbm / v_hbm;
    # v_out   pages are read through the inputs and written through these
    # scratch (in *refs):
    # k_buf   [2, Hkv, W*block_size, D] VMEM — double-buffered pages
    # v_buf
    # sems    DMA semaphores [2 slots, 2 (k/v), W pages]
    # m_ref   [Hkv, G, 128] f32 — running max (replicated over lanes)
    # l_ref   [Hkv, G, 128] f32 — running sum
    # acc_ref [Hkv, G, D] f32 — running weighted values
    # append only, all kept from one cell to the next:
    # k_stage [2, Hkv, block_size, D] VMEM — the page a lane writes back
    # v_stage
    # wsems   DMA semaphores [2 stages, 2 (k/v)]
    # pending [2] int32 SMEM — 1 while a stage's write-back is not waited for
    block_size: int,
    pages_per_chunk: int,
    scale: float,
    window: Optional[int],
    softcap: Optional[float],
    quantized: bool = False,
    append: bool = False,
):
    if quantized:
        ks_ref, vs_ref = refs[0], refs[1]
        refs = refs[2:]
    else:
        ks_ref = vs_ref = None
    if append:
        (q_ref, k_hbm, v_hbm, kn_ref, vn_ref, o_ref, k_out, v_out,
         k_buf, v_buf, sems, m_ref, l_ref, acc_ref,
         k_stage, v_stage, wsems, pending) = refs
    else:
        (q_ref, k_hbm, v_hbm, o_ref,
         k_buf, v_buf, sems, m_ref, l_ref, acc_ref) = refs
    Hkv, D = q_ref.shape[1], q_ref.shape[3]
    lanes_per_block = kn_ref.shape[0] if append else None
    b = pl.program_id(0)
    ctx_len = context_lens_ref[b]
    W = pages_per_chunk
    chunk_tokens = W * block_size
    n_chunks = lax.div(ctx_len + chunk_tokens - 1, chunk_tokens)
    last_page = jnp.maximum((ctx_len - 1) // block_size, 0)
    # sliding window: the query sits at ctx_len-1 and sees positions
    # [ctx_len - window, ctx_len); chunks wholly before that are never
    # fetched — per-step KV traffic is O(window), not O(context)
    if window is None:
        kv_start = jnp.int32(0)
        c_start = jnp.int32(0)
    else:
        kv_start = jnp.maximum(ctx_len - window, 0)
        c_start = lax.div(kv_start, chunk_tokens)
    # keys go to the MXU as stored (int8 mantissas widen exactly): a
    # product of two bfloat16 numbers is exact in the float32 accumulator
    key_dtype = q_ref.dtype if quantized else jnp.promote_types(
        q_ref.dtype, k_buf.dtype
    )

    def page_of(c, i):
        # page i of chunk c; pages past the end clamp to the last valid page
        # (fetched redundantly, masked in compute)
        return block_tables_ref[b, jnp.minimum(c * W + i, last_page)]

    def dma(c, slot, i, buf, hbm, kv):
        # every KV head's strip of the page in one descriptor
        return pltpu.make_async_copy(
            hbm.at[:, page_of(c, i)],
            buf.at[slot, :, pl.ds(i * block_size, block_size), :],
            sems.at[slot, kv, i],
        )

    def issue(c, slot):
        for i in range(W):  # static unroll: W outstanding copies each way
            dma(c, slot, i, k_buf, k_hbm, 0).start()
            dma(c, slot, i, v_buf, v_hbm, 1).start()

    def write_back(stage, page):
        # the staged page's strips, every KV head under one descriptor each
        return (
            pltpu.make_async_copy(
                k_stage.at[stage], k_out.at[:, page], wsems.at[stage, 0]
            ),
            pltpu.make_async_copy(
                v_stage.at[stage], v_out.at[:, page], wsems.at[stage, 1]
            ),
        )

    def settle(stage):
        # wait for the write-back a stage still has in flight (the wait
        # reads the semaphore and the size, not the page)
        @pl.when(pending[stage] == 1)
        def _():
            for copy in write_back(stage, 0):
                copy.wait()
            pending[stage] = 0

    def put_new_row(c, slot):
        # the new token's row into its page's strip, in the chunk just
        # fetched (the products read it there) and in this lane's stage,
        # which is copied to the page in HBM beside the lane's products
        stage = b % 2
        settle(stage)  # the lane two before this one, long done
        start = pl.multiple_of((last_page - c * W) * block_size, block_size)
        offset = (ctx_len - 1) % block_size
        for buf, new_ref, staged in (
            (k_buf, kn_ref, k_stage), (v_buf, vn_ref, v_stage)
        ):
            # a head at a time. The rows arrive as the projection lays
            # them, [lanes, Hkv * D] with a lane a sublane, `lanes_per_block`
            # lanes a block (a relayout to a lane's own [Hkv, D] tile would
            # be an XLA copy a layer): this lane's row is picked by a
            # masked maximum over the block's sublanes (against -inf the
            # row comes back bit for bit, a zero of either sign too)
            mine = lax.broadcasted_iota(
                jnp.int32, (lanes_per_block, D), 0
            ) == b % lanes_per_block
            for h in range(Hkv):
                rows = new_ref[:, h * D:(h + 1) * D].astype(jnp.float32)
                new = jnp.max(
                    jnp.where(mine, rows, -jnp.inf), axis=0, keepdims=True
                ).astype(buf.dtype)  # [1, D]
                strip = buf[slot, h, pl.ds(start, block_size), :]
                row = lax.broadcasted_iota(jnp.int32, strip.shape, 0)
                strip = jnp.where(row == offset, new, strip)
                buf[slot, h, pl.ds(start, block_size), :] = strip
                staged[stage, h] = strip
        for copy in write_back(stage, block_tables_ref[b, last_page]):
            copy.start()
        pending[stage] = 1

    if append:
        @pl.when(b == 0)
        def _first_cell():
            pending[0] = 0
            pending[1] = 0

    @pl.when(ctx_len <= 0)
    def _idle():  # no request in the lane: no page is read, the rows are zero
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(ctx_len > 0)
    def _live():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        issue(c_start, c_start % 2)

        def fold_chunk(c, last: Optional[bool]):
            # `last`: None where the loop does not know (it asks the
            # counter before it prefetches), else whether `c` is the
            # lane's last chunk, the one that holds the new row's page
            slot = c % 2

            if last is None:
                @pl.when(c + 1 < n_chunks)
                def _prefetch():
                    issue(c + 1, (c + 1) % 2)
            elif not last:
                issue(c + 1, (c + 1) % 2)

            for i in range(W):
                dma(c, slot, i, k_buf, k_hbm, 0).wait()
                dma(c, slot, i, v_buf, v_hbm, 1).wait()
            if last:
                put_new_row(c, slot)

            q = q_ref[0].astype(key_dtype)  # [Hkv, G, D]
            k = k_buf[slot].astype(key_dtype)  # [Hkv, W*bs, D]
            v = v_buf[slot].astype(jnp.float32)
            s = lax.dot_general(
                q, k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            ) * scale  # [Hkv, G, W*bs]
            if quantized:
                # in-kernel dequant: one SMEM scale per fetched (head,
                # page). A page's scale is constant over its rows, so it
                # factors out of both dots: q.(k*s) = (q.k)*s and
                # p.(v*s) = (p*s).v — applied to the [G, W*bs]
                # scores/probabilities, not the [W*bs, D] tiles
                pages = [page_of(c, i) for i in range(W)]

                def page_scales(ref):  # [Hkv, 1, W*bs]
                    return jnp.stack([
                        _page_scale_row(
                            [ref[h, page] for page in pages], block_size
                        )
                        for h in range(Hkv)
                    ])

                kcol, vcol = page_scales(ks_ref), page_scales(vs_ref)
                s = s * kcol
            s = _apply_softcap(s, softcap)
            pos = c * chunk_tokens + lax.broadcasted_iota(
                jnp.int32, s.shape, dimension=2
            )
            valid = pos < ctx_len
            if window is not None:
                valid &= pos >= kv_start
            s = jnp.where(valid, s, NEG_INF)

            m_prev = m_ref[:, :, :1]  # [Hkv, G, 1]
            l_prev = l_ref[:, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + lax.dot_general(
                p * vcol if quantized else p, v,
                (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
            return 0

        if append:
            lax.fori_loop(
                c_start, n_chunks - 1, lambda c, _: fold_chunk(c, False), 0
            )
            fold_chunk(n_chunks - 1, True)
        else:
            lax.fori_loop(
                c_start, n_chunks, lambda c, _: fold_chunk(c, None), 0
            )
        o_ref[0] = (acc_ref[...] / l_ref[:, :, :1]).astype(o_ref.dtype)

    if append:
        @pl.when(b == pl.num_programs(0) - 1)
        def _last_cell():  # nothing is in flight when the call returns
            settle(0)
            settle(1)


def paged_decode_attention_pallas(
    q: jax.Array,  # [B, Hq, D]
    k_cache: jax.Array,  # [Hkv, num_blocks, block_size, D] (head-major)
    v_cache: jax.Array,
    block_tables: jax.Array,  # [B, max_blocks] int32
    context_lens: jax.Array,  # [B] int32, INCLUDING the token just written;
    # 0 = the lane holds no request and gets zeros
    *,
    k_new: Optional[jax.Array] = None,  # [B, Hkv, D]: the token's rows,
    v_new: Optional[jax.Array] = None,  # appended by this call when given
    k_scales: Optional[jax.Array] = None,  # [Hkv, num_blocks] f32 — int8
    v_scales: Optional[jax.Array] = None,  # resident cache when given
    pages_per_chunk: int = DECODE_PAGES_PER_CHUNK,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    logit_softcap: Optional[float] = None,
    interpret: bool = False,
):
    """Flash paged decode attention; numerics match the XLA reference for
    every feature combination (window / scale / softcap).

    With `k_scales`/`v_scales`, the cache holds int8 mantissas: the kernel
    DMAs the int8 pages (half the HBM traffic) and multiplies each page's
    scalar-prefetched scale onto the VMEM tile inside the online-softmax
    loop — bf16 K/V never materializes in HBM.

    With `k_new`/`v_new` (a plain cache only) the call also appends: each
    live lane's row goes to position `context_lens - 1` of its table, the
    lane attends over it, and the result is `(attn, k_cache, v_cache)`
    with the caches aliased onto the arguments: written where they lie
    when the caller donates them. What `ops.attention.write_decode_kv`
    followed by this call without the rows gives, but for the null block,
    which an idle lane no longer writes."""
    B, Hq, D = q.shape
    Hkv, num_blocks, block_size, _ = k_cache.shape
    G = Hq // Hkv
    quantized = k_scales is not None
    append = k_new is not None
    assert not (append and quantized), "an int8 page's scale is not a row"
    max_blocks = block_tables.shape[1]
    W = max(1, min(pages_per_chunk, max_blocks))
    sc = float(scale) if scale is not None else 1.0 / float(D) ** 0.5

    def lane(b, *prefetch):  # units are blocks: one lane, all of its heads
        return (b, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, Hkv, G, D), lane),
        pl.BlockSpec(memory_space=pl.ANY),  # K cache stays in HBM
        pl.BlockSpec(memory_space=pl.ANY),  # V cache stays in HBM
    ]
    out_specs = pl.BlockSpec((1, Hkv, G, D), lane)
    out_shape = jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype)
    scratch_shapes = [
        pltpu.VMEM((2, Hkv, W * block_size, D), k_cache.dtype),
        pltpu.VMEM((2, Hkv, W * block_size, D), v_cache.dtype),
        pltpu.SemaphoreType.DMA((2, 2, W)),
        pltpu.VMEM((Hkv, G, 128), jnp.float32),
        pltpu.VMEM((Hkv, G, 128), jnp.float32),
        pltpu.VMEM((Hkv, G, D), jnp.float32),
    ]
    if append:
        # the new rows, eight lanes a block (one tile of sublanes; a batch
        # that is no multiple of eight is one block)
        R = 8 if B % 8 == 0 else B
        in_specs += [pl.BlockSpec((R, Hkv * D), lambda b, *_: (b // R, 0))] * 2
        out_specs = [out_specs] + [pl.BlockSpec(memory_space=pl.ANY)] * 2
        out_shape = [
            out_shape,
            jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
            jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype),
        ]
        scratch_shapes += [
            pltpu.VMEM((2, Hkv, block_size, D), k_cache.dtype),
            pltpu.VMEM((2, Hkv, block_size, D), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),
        ]
    kernel = pl.pallas_call(
        functools.partial(
            _decode_kernel,
            block_size=block_size,
            pages_per_chunk=W,
            scale=sc,
            window=int(window) if window is not None else None,
            softcap=float(logit_softcap) if logit_softcap is not None else None,
            quantized=quantized,
            append=append,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4 if quantized else 2,
            grid=(B,),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch_shapes,
        ),
        out_shape=out_shape,
        # operands are counted with the two scalar-prefetch ones: the
        # caches are the fourth and fifth, and outputs one and two
        input_output_aliases={3: 1, 4: 2} if append else {},
        compiler_params=pltpu.CompilerParams(
            # a write-back is waited for by a later cell: the cells of the
            # appending form run in order on one core
            dimension_semantics=("arbitrary" if append else "parallel",),
        ),
        interpret=interpret,
    )
    q_grouped = q.reshape(B, Hkv, G, D)
    prefetch = [
        block_tables.astype(jnp.int32),
        context_lens.astype(jnp.int32),
    ]
    if quantized:
        prefetch += [
            k_scales.astype(jnp.float32), v_scales.astype(jnp.float32)
        ]
    if not append:
        out = run_kernel(kernel, *prefetch, q_grouped, k_cache, v_cache)
        return out.reshape(B, Hq, D)
    out, k_cache, v_cache = run_kernel(
        kernel, *prefetch, q_grouped, k_cache, v_cache,
        k_new.astype(k_cache.dtype).reshape(B, Hkv * D),
        v_new.astype(v_cache.dtype).reshape(B, Hkv * D),
    )
    return out.reshape(B, Hq, D), k_cache, v_cache


# ---------------------------------------------------------- paged verify


def _verify_kernel(
    # scalar prefetch
    block_tables_ref,  # [B, max_blocks] int32 (SMEM)
    positions_ref,  # [B, S] int32 (SMEM) — consecutive per lane
    # quantized mode inserts [Hkv, num_blocks] f32 k/v scale planes here,
    # then the usual refs follow:
    *refs,
    # inputs (in *refs):
    # q_ref   [1, 1, S*G, D] VMEM — this lane+head's draft-window queries
    # k_hbm   [Hkv, num_blocks, block_size, D] (int8 when quantized)
    # v_hbm
    # o_ref   [1, 1, S*G, D] blocked output
    # scratch: k_buf, v_buf, sems, m_ref [S*G, 128], l_ref, acc_ref
    block_size: int,
    pages_per_chunk: int,
    num_spec: int,  # S
    group: int,  # G
    max_blocks: int,
    scale: float,
    window: Optional[int],
    softcap: Optional[float],
    quantized: bool = False,
):
    if quantized:
        ks_ref, vs_ref = refs[0], refs[1]
        refs = refs[2:]
    else:
        ks_ref = vs_ref = None
    (q_ref, k_hbm, v_hbm, o_ref,
     k_buf, v_buf, sems, m_ref, l_ref, acc_ref) = refs
    b = pl.program_id(0)
    h = pl.program_id(1)
    W = pages_per_chunk
    chunk_tokens = W * block_size
    # per-lane draft positions are consecutive (qpos = base + s — what
    # decode_verify feeds); the last query bounds the live context
    base = positions_ref[b, 0]
    ctx_len = positions_ref[b, num_spec - 1] + 1
    n_chunks = lax.div(ctx_len + chunk_tokens - 1, chunk_tokens)
    last_page = jnp.clip((ctx_len - 1) // block_size, 0, max_blocks - 1)
    # earliest KV any query in the window can see: base - window + 1
    if window is None:
        c_start = jnp.int32(0)
    else:
        kv_start = jnp.maximum(base - (window - 1), 0)
        c_start = lax.div(kv_start, chunk_tokens)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def dma(c, slot, i, buf, hbm, kv):
        page = block_tables_ref[b, jnp.minimum(c * W + i, last_page)]
        return pltpu.make_async_copy(
            hbm.at[h, page],
            buf.at[slot, pl.ds(i * block_size, block_size), :],
            sems.at[slot, kv, i],
        )

    def issue(c, slot):
        for i in range(W):
            dma(c, slot, i, k_buf, k_hbm, 0).start()
            dma(c, slot, i, v_buf, v_hbm, 1).start()

    @pl.when(n_chunks > c_start)
    def _go():
        issue(c_start, c_start % 2)

        def loop_body(c, _):
            slot = c % 2

            @pl.when(c + 1 < n_chunks)
            def _prefetch():
                issue(c + 1, (c + 1) % 2)

            for i in range(W):
                dma(c, slot, i, k_buf, k_hbm, 0).wait()
                dma(c, slot, i, v_buf, v_hbm, 1).wait()

            q = q_ref[0, 0].astype(jnp.float32)  # [S*G, D]
            k = k_buf[slot].astype(jnp.float32)  # [W*bs, D]
            v = v_buf[slot].astype(jnp.float32)
            if quantized:
                kvals = []
                vvals = []
                for i in range(W):
                    page = block_tables_ref[
                        b, jnp.minimum(c * W + i, last_page)
                    ]
                    kvals.append(ks_ref[h, page])
                    vvals.append(vs_ref[h, page])
                kcol = _page_scale_row(kvals, block_size)
                vcol = _page_scale_row(vvals, block_size)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [S*G, W*bs]
            if quantized:
                s = s * kcol
            s = _apply_softcap(s, softcap)
            # row r is draft position r // G at true position base + r//G
            qpos = base + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, dimension=0
            ) // group
            kpos = c * chunk_tokens + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, dimension=1
            )
            valid = kpos <= qpos
            if window is not None:
                valid &= qpos - kpos < window
            s = jnp.where(valid, s, NEG_INF)

            m_prev = m_ref[:, :1]
            l_prev = l_ref[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p * vcol if quantized else p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
            return 0

        lax.fori_loop(c_start, n_chunks, loop_body, 0)

    l = l_ref[:, :1]
    safe_l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


def paged_verify_attention_pallas(
    q: jax.Array,  # [B, S, Hq, D] — S speculative positions per sequence
    k_cache: jax.Array,  # [Hkv, num_blocks, block_size, D] (head-major)
    v_cache: jax.Array,
    block_tables: jax.Array,  # [B, max_blocks] int32
    positions: jax.Array,  # [B, S] int32 — CONSECUTIVE per lane
    *,
    k_scales: Optional[jax.Array] = None,  # [Hkv, num_blocks] f32 — int8
    v_scales: Optional[jax.Array] = None,  # resident cache when given
    pages_per_chunk: int = 8,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    logit_softcap: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """Flash paged attention for the spec-decode verify pass: the S draft
    positions of each lane stream the lane's pages once (the decode
    kernel's DMA pattern amortized over the whole draft window) instead of
    the XLA path's dense [Hkv, B, S_ctx, D] gather. With scale planes the
    pages are int8-resident and dequantized in-kernel (see decode kernel).

    Assumes each lane's positions are consecutive (positions[b, s] =
    positions[b, 0] + s) — exactly what llama.decode_verify feeds; the
    dispatcher in ops/attention.py only routes that shape here.
    """
    B, S, Hq, D = q.shape
    Hkv, num_blocks, block_size, _ = k_cache.shape
    G = Hq // Hkv
    quantized = k_scales is not None
    max_blocks = block_tables.shape[1]
    W = max(1, min(pages_per_chunk, max_blocks))
    sc = float(scale) if scale is not None else 1.0 / float(D) ** 0.5

    def q_index(b, h, *prefetch):
        return (b, h, 0, 0)

    def o_index(b, h, *prefetch):
        return (b, h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 if quantized else 2,
        grid=(B, Hkv),
        in_specs=[
            pl.BlockSpec((1, 1, S * G, D), q_index),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, S * G, D), o_index),
        scratch_shapes=[
            pltpu.VMEM((2, W * block_size, D), k_cache.dtype),
            pltpu.VMEM((2, W * block_size, D), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, 2, W)),
            pltpu.VMEM((S * G, 128), jnp.float32),
            pltpu.VMEM((S * G, 128), jnp.float32),
            pltpu.VMEM((S * G, D), jnp.float32),
        ],
    )
    kernel = pl.pallas_call(
        functools.partial(
            _verify_kernel,
            block_size=block_size,
            pages_per_chunk=W,
            num_spec=S,
            group=G,
            max_blocks=max_blocks,
            scale=sc,
            window=int(window) if window is not None else None,
            softcap=float(logit_softcap) if logit_softcap is not None else None,
            quantized=quantized,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, S * G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )
    # [B, S, Hkv, G, D] -> [B, Hkv, S, G, D] -> rows are (draft pos, group)
    q_grouped = (
        q.reshape(B, S, Hkv, G, D).transpose(0, 2, 1, 3, 4).reshape(
            B, Hkv, S * G, D
        )
    )
    prefetch = [
        block_tables.astype(jnp.int32),
        positions.astype(jnp.int32),
    ]
    if quantized:
        prefetch += [
            k_scales.astype(jnp.float32), v_scales.astype(jnp.float32)
        ]
    out = run_kernel(kernel, *prefetch, q_grouped, k_cache, v_cache)
    return (
        out.reshape(B, Hkv, S, G, D).transpose(0, 2, 1, 3, 4).reshape(
            B, S, Hq, D
        )
    )


# --------------------------------------------------------- flash prefill


def flash_prefill_attention_pallas(
    q: jax.Array,  # [P, Hq, D]
    k: jax.Array,  # [P, Hkv, D]
    v: jax.Array,
    valid_len: jax.Array,  # scalar int32
    *,
    block_q: int = 128,
    block_k: int = 128,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    logit_softcap: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """Blockwise causal flash attention for the prefill pass (GQA-aware).

    Requires P % block_q == 0 (callers pad prompts to the KV page size and
    choose block sizes accordingly). KV heads are the outer grid dim; q is
    group-expanded so each kv head attends its [G * P, D] query slab.

    Sliding window: k blocks wholly left of a q block's window (every pair
    has qpos - kpos >= window) are skipped — no compute AND no DMA (the
    index map clamps them onto the window's first block, so Mosaic's
    repeated-index rule elides the copies). Prefill FLOPs/traffic are
    O(P * window) instead of O(P^2).
    """
    P, Hq, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    block_q = min(block_q, P)
    block_k = min(block_k, P)
    assert P % block_q == 0 and P % block_k == 0
    sc = float(scale) if scale is not None else 1.0 / float(D) ** 0.5
    win = int(window) if window is not None else None
    softcap = float(logit_softcap) if logit_softcap is not None else None

    # [P, Hkv, G, D] -> [Hkv, P, G, D] -> per-head queries stay position-major
    qh = q.reshape(P, Hkv, G, D).transpose(1, 0, 2, 3)  # [Hkv, P, G, D]
    kh = k.transpose(1, 0, 2)  # [Hkv, P, D]
    vh = v.transpose(1, 0, 2)

    def q_index(h, iq, jk, vl):
        return (h, iq, 0, 0)

    def kv_index(h, iq, jk, vl):
        # Clamp skipped k blocks (acausal, fully padded, or wholly left of
        # the sliding window) to a fetched one so their DMAs are elided
        # (repeated index rule).
        causal_last = (iq * block_q + block_q - 1) // block_k
        valid_last = jnp.maximum((vl[0] - 1) // block_k, 0)
        jj = jnp.minimum(jk, jnp.minimum(causal_last, valid_last))
        if win is not None:
            win_first = jnp.maximum(iq * block_q - (win - 1), 0) // block_k
            jj = jnp.maximum(jj, jnp.minimum(win_first, causal_last))
        return (h, jj, 0)

    def o_index(h, iq, jk, vl):
        return (h, iq, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Hkv, P // block_q, P // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, G, D), q_index),
            pl.BlockSpec((1, block_k, D), kv_index),
            pl.BlockSpec((1, block_k, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, G, D), o_index),
        scratch_shapes=[
            pltpu.VMEM((block_q * G, 128), jnp.float32),
            pltpu.VMEM((block_q * G, 128), jnp.float32),
            pltpu.VMEM((block_q * G, D), jnp.float32),
        ],
    )

    def kernel_body(vl_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
        # flatten the group dim into rows: [1, bq, G, D] -> [bq*G, D]; causal
        # positions are per q row (each group row shares its token position)
        iq = pl.program_id(1)
        jk = pl.program_id(2)
        valid_len = vl_ref[0]

        @pl.when(jk == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        live = (
            (jk * block_k <= iq * block_q + block_q - 1)
            & (jk * block_k < valid_len)
        )
        if win is not None:
            # block-level window test: the block's NEWEST k vs this q
            # block's OLDEST query — false means every (q, k) pair in the
            # tile is out of window
            live &= jk * block_k + block_k - 1 >= iq * block_q - (win - 1)

        @pl.when(live)
        def _attend():
            qb = q_ref[0].astype(jnp.float32).reshape(block_q * G, D)
            kb = k_ref[0].astype(jnp.float32)  # [bk, D]
            vb = v_ref[0].astype(jnp.float32)
            s = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sc  # [bq*G, bk]
            s = _apply_softcap(s, softcap)
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // G
            qpos = iq * block_q + row
            kpos = jk * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = (kpos <= qpos) & (kpos < valid_len)
            if win is not None:
                mask &= qpos - kpos < win
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[:, :1]
            l_prev = l_ref[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[...] = jnp.broadcast_to(
                l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape
            )
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p, vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

        @pl.when(jk == pl.num_programs(2) - 1)
        def _finish():
            l = l_ref[:, :1]
            safe_l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0] = (
                (acc_ref[...] / safe_l).reshape(block_q, G, D).astype(o_ref.dtype)
            )

    kernel = pl.pallas_call(
        kernel_body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Hkv, P, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )
    out = run_kernel(
        kernel, jnp.asarray(valid_len, jnp.int32).reshape(1), qh, kh, vh
    )  # [Hkv, P, G, D]
    return out.transpose(1, 0, 2, 3).reshape(P, Hq, D)
