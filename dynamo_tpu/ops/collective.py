"""Meshed fused decode: the fused decode kernels under shard_map over the
tp axis, plus the decomposed collective-matmul tail (ISSUE 19).

The PR-9 fused kernels (`ops/linear.py`) used to require `mesh is None`:
on any multi-chip mesh decode silently fell back to the unfused op chain,
losing the fusion win exactly where the decode-MFU roadmap item says it
matters. The wrappers here run the SAME pallas programs per shard —
weights are already head/column-sharded by `parallel/sharding.py`
(Megatron layout: wq/wk/wv column-parallel, wo/wd row-parallel, int8
scale planes riding their mantissas' sharding), so each chip executes
the fused program on its head/feature slice and only the row-parallel
projections need a tp-axis reduction.

Two reduction strategies:

  * plain (`fused_attn_out_residual_meshed`, the bit-exact default): the
    o-proj partial products are psum'd in f32 BEFORE the scale/cast/
    residual elementwise — the same placement GSPMD picks for the
    unfused sharded matmul, so fused-vs-unfused stays bit-comparable.
  * decomposed collective-matmul (`fused_tail_overlap`,
    `DYN_COLLECTIVE_OVERLAP=1`): the two per-layer all-reduces (o-proj,
    down-proj) are decomposed into reduce-scatter + all-gather rings
    whose hops are pipelined against matmul chunks — the o-proj runs one
    fused pallas program per output chunk with the f32 partial ring
    riding behind the next chunk's matmul, the post-attention RMSNorm
    runs on scattered chunks (variance via one scalar psum), the normed
    chunks all-gather through a ppermute ring hidden behind the gate/up
    projection chunks, and the down-proj reduce-scatters the same way
    behind its own column chunks. Only the final [B, hidden/tp] output
    all-gather is exposed. Ring summation reorders the f32 adds, so this
    path is token-identical (not bit-identical) to the plain psum path.

`tests/test_meshed_fused.py` holds the parity bars.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as PSpec

from dynamo_tpu.ops.basics import swiglu
from dynamo_tpu.ops.linear import (
    _wq_parts,
    fused_attn_out_residual,
    fused_qkv_rope,
)


def fused_qkv_rope_meshed(
    mesh,
    x: jax.Array,  # [B, hidden] residual stream (replicated)
    attn_norm: jax.Array,
    wq, wk, wv,
    cos: jax.Array,
    sin: jax.Array,
    *,
    eps: float,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    bq: Optional[jax.Array] = None,
    bk: Optional[jax.Array] = None,
    bv: Optional[jax.Array] = None,
    block_in: Optional[int] = None,
    interpret: bool = False,
    axis: str = "tp",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """`fused_qkv_rope` under shard_map: each shard runs the fused program
    on its head slice (column-parallel projections need no collective —
    the full contraction dim is resident per shard), so the outputs come
    back head-sharded exactly like the unfused GSPMD path and feed the
    shard_map'd paged attention without a reshard."""
    tp = mesh.shape[axis]
    assert num_heads % tp == 0 and num_kv_heads % tp == 0, (
        num_heads, num_kv_heads, tp,
    )
    wq_q, wq_s = _wq_parts(wq)
    wk_q, wk_s = _wq_parts(wk)
    wv_q, wv_s = _wq_parts(wv)
    quantized = wq_s is not None
    has_bias = bq is not None

    rep2 = PSpec(None, None)
    col = PSpec(None, axis)
    vec = PSpec(axis)
    args = [x, attn_norm, wq_q, wk_q, wv_q]
    specs = [rep2, PSpec(None), col, col, col]
    if quantized:
        args += [wq_s, wk_s, wv_s]
        specs += [vec, vec, vec]
    if has_bias:
        args += [bq, bk, bv]
        specs += [vec, vec, vec]
    args += [cos, sin]
    specs += [rep2, rep2]

    def _body(*local):
        it = iter(local)
        xl, nw = next(it), next(it)
        mq, mk, mv = next(it), next(it), next(it)
        if quantized:
            sq, sk, sv = next(it), next(it), next(it)
            lwq = {"q": mq, "s": sq}
            lwk = {"q": mk, "s": sk}
            lwv = {"q": mv, "s": sv}
        else:
            lwq, lwk, lwv = mq, mk, mv
        lbq = lbk = lbv = None
        if has_bias:
            lbq, lbk, lbv = next(it), next(it), next(it)
        cosl, sinl = next(it), next(it)
        return fused_qkv_rope(
            xl, nw, lwq, lwk, lwv, cosl, sinl,
            eps=eps,
            num_heads=num_heads // tp,
            num_kv_heads=num_kv_heads // tp,
            head_dim=head_dim,
            bq=lbq, bk=lbk, bv=lbv,
            block_in=block_in, interpret=interpret,
        )

    head_spec = PSpec(None, axis, None)
    return shard_map(
        _body, mesh=mesh, in_specs=tuple(specs),
        out_specs=(head_spec, head_spec, head_spec), check_vma=False,
    )(*args)


def fused_attn_out_residual_meshed(
    mesh,
    attn: jax.Array,  # [B, q_dim] flat attention output (head-sharded)
    wo,
    x: jax.Array,  # [B, hidden] residual stream (replicated)
    *,
    block_in: Optional[int] = None,
    interpret: bool = False,
    axis: str = "tp",
) -> jax.Array:
    """`fused_attn_out_residual` under shard_map (row-parallel o-proj):
    each shard's fused program emits the raw f32 partial product, the tp
    axis psums in f32, and the per-channel scale / cast / residual apply
    to the reduced sum — GSPMD's all-reduce placement for the unfused
    path, so the two stay bit-comparable."""
    wo_q, wo_s = _wq_parts(wo)
    quantized = wo_s is not None
    args = [attn, wo_q, x]
    specs = [PSpec(None, axis), PSpec(axis, None), PSpec(None, None)]
    if quantized:
        args.append(wo_s)
        specs.append(PSpec(None))

    def _body(*local):
        it = iter(local)
        attn_l, wo_l, xl = next(it), next(it), next(it)
        so = next(it) if quantized else None
        partial = fused_attn_out_residual(
            attn_l, wo_l, partial_out=True,
            block_in=block_in, interpret=interpret,
        )
        red = jax.lax.psum(partial, axis)
        if so is not None:
            y = (red * so.astype(jnp.float32)).astype(xl.dtype)
        else:
            y = red.astype(xl.dtype)
        return xl + y

    return shard_map(
        _body, mesh=mesh, in_specs=tuple(specs),
        out_specs=PSpec(None, None), check_vma=False,
    )(*args)


def fused_tail_overlap(
    mesh,
    attn: jax.Array,  # [B, q_dim] flat attention output (head-sharded)
    wo,
    x: jax.Array,  # [B, hidden] residual stream (replicated)
    mlp_norm: jax.Array,
    wg, wu, wd,
    *,
    eps: float,
    mlp_act: str = "silu",
    interpret: bool = False,
    axis: str = "tp",
) -> jax.Array:
    """The whole post-attention layer tail — o-proj + residual + MLP norm
    + gate/up/act/down + residual — with both tp all-reduces decomposed
    into rings pipelined against matmul chunks (see module docstring).
    Returns the post-MLP residual stream, replicated."""
    tp = mesh.shape[axis]
    wo_q, wo_s = _wq_parts(wo)
    wg_q, wg_s = _wq_parts(wg)
    wu_q, wu_s = _wq_parts(wu)
    wd_q, wd_s = _wq_parts(wd)
    H = wo_q.shape[1]
    assert H % tp == 0, (H, tp)
    chunk = H // tp

    args = [attn, wo_q, x, mlp_norm, wg_q, wu_q, wd_q]
    specs = [
        PSpec(None, axis),  # attn (head-sharded, flat)
        PSpec(axis, None),  # wo rows
        PSpec(None, None),  # x replicated
        PSpec(None),  # mlp_norm replicated
        PSpec(None, axis),  # wg cols
        PSpec(None, axis),  # wu cols
        PSpec(axis, None),  # wd rows
    ]
    for s in (wo_s, wg_s, wu_s, wd_s):
        if s is not None:
            args.append(s)
    if wo_s is not None:
        specs.append(PSpec(None))  # per-out-channel, rows sharded
    if wg_s is not None:
        specs.append(PSpec(axis))
    if wu_s is not None:
        specs.append(PSpec(axis))
    if wd_s is not None:
        specs.append(PSpec(None))

    ring_fwd = [(j, (j + 1) % tp) for j in range(tp)]
    ring_bwd = [(j, (j - 1) % tp) for j in range(tp)]

    def _body(*local):
        it = iter(local)
        attn_l, wo_l, xl, nw = next(it), next(it), next(it), next(it)
        wg_l, wu_l, wd_l = next(it), next(it), next(it)
        so = next(it) if wo_s is not None else None
        sg = next(it) if wg_s is not None else None
        su = next(it) if wu_s is not None else None
        sd = next(it) if wd_s is not None else None
        dtype = xl.dtype
        d = jax.lax.axis_index(axis)

        # --- o-proj ring reduce-scatter collective-matmul: one fused
        # pallas program per output chunk, the running f32 partial
        # ppermuting behind the NEXT chunk's matmul; after tp steps each
        # shard holds its own chunk fully reduced
        acc = None
        for k in range(tp):
            c = (d + 1 + k) % tp
            cols = jax.lax.dynamic_slice_in_dim(wo_l, c * chunk, chunk, 1)
            p = fused_attn_out_residual(
                attn_l, cols, partial_out=True, interpret=interpret
            )
            acc = p if acc is None else acc + p
            if k < tp - 1:
                acc = jax.lax.ppermute(acc, axis, perm=ring_bwd)
        if so is not None:
            s_c = jax.lax.dynamic_slice_in_dim(so, d * chunk, chunk, 0)
            y_c = (acc * s_c.astype(jnp.float32)).astype(dtype)
        else:
            y_c = acc.astype(dtype)
        h_c = jax.lax.dynamic_slice_in_dim(xl, d * chunk, chunk, 1) + y_c

        # --- RMSNorm on scattered chunks: full-row variance via one
        # scalar-sized psum (ops/basics.rms_norm's f32 arithmetic)
        hf = h_c.astype(jnp.float32)
        ssq = jax.lax.psum(jnp.sum(hf * hf, axis=-1), axis)
        inv = jax.lax.rsqrt(ssq / H + eps)
        nw_c = jax.lax.dynamic_slice_in_dim(nw, d * chunk, chunk, 0)
        n_c = (hf * inv[:, None] * nw_c.astype(jnp.float32)).astype(dtype)

        # --- gate/up collective-matmul: all-gather the normed chunks
        # through a ppermute ring, each hop hidden behind the matmul of
        # the chunk already in hand against its wg/wu row slice
        g_acc = u_acc = None
        cur = n_c
        for k in range(tp):
            src = (d - k) % tp
            rows_g = jax.lax.dynamic_slice_in_dim(wg_l, src * chunk, chunk, 0)
            rows_u = jax.lax.dynamic_slice_in_dim(wu_l, src * chunk, chunk, 0)
            pg = jax.lax.dot_general(
                cur, rows_g.astype(dtype), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            pu = jax.lax.dot_general(
                cur, rows_u.astype(dtype), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            g_acc = pg if g_acc is None else g_acc + pg
            u_acc = pu if u_acc is None else u_acc + pu
            if k < tp - 1:
                cur = jax.lax.ppermute(cur, axis, perm=ring_fwd)
        gate = (
            (g_acc * sg.astype(jnp.float32)).astype(dtype)
            if sg is not None else g_acc.astype(dtype)
        )
        up = (
            (u_acc * su.astype(jnp.float32)).astype(dtype)
            if su is not None else u_acc.astype(dtype)
        )
        if mlp_act == "gelu_tanh":  # Gemma GeGLU (models/llama._mlp)
            act = jax.nn.gelu(
                gate.astype(jnp.float32), approximate=True
            ).astype(gate.dtype) * up
        else:
            act = swiglu(gate, up)

        # --- down-proj ring reduce-scatter collective-matmul, same
        # schedule as the o-proj ring
        acc2 = None
        for k in range(tp):
            c = (d + 1 + k) % tp
            cols = jax.lax.dynamic_slice_in_dim(wd_l, c * chunk, chunk, 1)
            p = jax.lax.dot_general(
                act, cols.astype(act.dtype), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc2 = p if acc2 is None else acc2 + p
            if k < tp - 1:
                acc2 = jax.lax.ppermute(acc2, axis, perm=ring_bwd)
        if sd is not None:
            s_c2 = jax.lax.dynamic_slice_in_dim(sd, d * chunk, chunk, 0)
            y2_c = (acc2 * s_c2.astype(jnp.float32)).astype(dtype)
        else:
            y2_c = acc2.astype(dtype)
        out_c = h_c + y2_c

        # the only exposed collective: gather the final [B, chunk] output
        # chunks back to the replicated residual stream
        return jax.lax.all_gather(out_c, axis, axis=1, tiled=True)

    return shard_map(
        _body, mesh=mesh, in_specs=tuple(specs),
        out_specs=PSpec(None, None), check_vma=False,
    )(*args)
