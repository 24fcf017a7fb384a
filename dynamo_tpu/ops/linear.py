"""Linear layers with optional int8 weight-only quantization + the fused
decode-step kernels.

On v5e-class chips (16 GB HBM) an 8B bf16 model does not leave room for KV
cache, and decode is weight-bandwidth-bound anyway — int8 weights halve both
footprint and HBM traffic. Weights are stored per-output-channel quantized
({"q": int8 [in,out], "s": bf16 [out]}); XLA fuses the int8->bf16 convert and
scale into the matmul's operand loads, so the MXU still sees bf16 tiles.

Accumulation dtypes (documented contract):

  * bf16 activations x int8 weights: the mantissas are widened to bf16
    (lossless — |q| <= 127 is exact in bf16) and the dot accumulates in
    f32 (`preferred_element_type`), then the per-channel scale applies in
    f32 before the cast back to bf16.
  * int8 activations x int8 weights (dynamic activation quant callers):
    the dot accumulates EXACTLY in int32 — no rounding until the scales
    apply. This is the "where shapes allow" fast path: both operands must
    be integral.

The fused decode kernels (`fused_qkv_rope`, `fused_attn_out_residual`)
collapse the per-layer decode hot path from many small programs into two:
RMSNorm + the three QKV projections (+bias) + RoPE in one pallas launch,
and the attention-output projection + residual add in another — the
int8->f32 dequant happens on the weight tiles in VMEM, and the [B, hidden]
activations never round-trip HBM between the fused ops. The kernels follow
the SAME op/precision sequence as the unfused path (rms_norm -> matmul
f32-accum -> scale -> bf16 cast -> bias -> rope-in-f32), so with a single
contraction tile fused and unfused decode are bit-identical. The tile is
the whole contraction dim while the weights fit VMEM and a divisor of it
otherwise (`_contraction_tile`; a 7B model's projections do not fit).
"""

from __future__ import annotations

import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Params = Union[jax.Array, dict]


def quantize_int8(w: jax.Array) -> dict:
    """Per-output-channel symmetric int8 quantization of [in, out] weights.

    All-zero (or otherwise degenerate) channels get scale 1.0 instead of
    amax/127 = 0: quantized values are 0 either way, but the stored scale
    stays finite so downstream `1/scale` users can never see inf/nan."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127).astype(
        jnp.int8
    )
    return {"q": q, "s": scale.astype(jnp.bfloat16)}


def linear(x: jax.Array, w: Params) -> jax.Array:
    """x @ w for bf16 or int8-quantized weights (see module docstring for
    the accumulation-dtype contract)."""
    if isinstance(w, dict):
        if jnp.issubdtype(x.dtype, jnp.integer):
            # int8 x int8: exact int32 accumulation, scales apply after
            y = jnp.matmul(x, w["q"], preferred_element_type=jnp.int32)
            return y.astype(jnp.float32) * w["s"].astype(jnp.float32)
        y = jnp.matmul(
            x, w["q"].astype(x.dtype), preferred_element_type=jnp.float32
        )
        return (y * w["s"].astype(jnp.float32)).astype(x.dtype)
    return jnp.matmul(x, w.astype(x.dtype))


def maybe_quantize(w: jax.Array, quantize: bool) -> Params:
    return quantize_int8(w) if quantize else w


# ------------------------------------------------------- fused decode step
#
# Decode is dispatch-bound as much as bandwidth-bound: each layer's hot
# path was norm -> 3 matmuls -> bias -> rope (5+ programs) and attn-out ->
# o-proj -> residual (2+). These two kernels collapse them; the weight
# dequant rides the operand load exactly like the unfused path.

# Trace-time fused-kernel entry counters: bumped every time a fused
# wrapper is TRACED into a program (once per compile, not per step — jit
# caches traces). tests/test_meshed_fused.py resets then reads these to
# prove a meshed decode program actually contains the fused kernels
# instead of silently falling back to the unfused op chain.
FUSED_KERNEL_ENTRIES: dict = {"qkv_rope": 0, "attn_out": 0}


def reset_fused_kernel_entries() -> None:
    for key in FUSED_KERNEL_ENTRIES:
        FUSED_KERNEL_ENTRIES[key] = 0


def _wq_parts(w: Params):
    """(mantissas/weights, scale | None) for a maybe-quantized weight."""
    if isinstance(w, dict):
        return w["q"], w["s"]
    return w, None


def _mm_tile(x, w, acc):
    """One contraction tile: f32-accumulating dot, int8 widened to the
    activation dtype first (matches `linear`)."""
    return acc + jax.lax.dot_general(
        x, w.astype(x.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _finish(acc, s, bias, dtype):
    """Scale -> cast -> bias, in the unfused path's exact order/dtypes."""
    if s is not None:
        y = (acc * s.astype(jnp.float32)).astype(dtype)
    else:
        y = acc.astype(dtype)
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y


def _rope_rotate(y, cos, sin, heads, head_dim, dtype):
    """apply_rope's rotation on a flat [B, heads*head_dim] projection,
    given precomputed cos/sin [B, head_dim//2] (same formula, f32)."""
    B = y.shape[0]
    yh = y.reshape(B, heads, head_dim).astype(jnp.float32)
    x1, x2 = jnp.split(yh, 2, axis=-1)
    c = cos[:, None, :]
    s = sin[:, None, :]
    return jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1
    ).astype(dtype)


# One contraction tile of a fused kernel's weights may take this much
# VMEM (Mosaic double-buffers it): 16 MB of VMEM on a v5e must also hold
# the activations and the f32 accumulators.
_WEIGHT_TILE_BYTES = 4 * 2**20


def _contraction_tile(k_dim: int, row_bytes: int) -> int:
    """Rows of the contraction dim per grid step: all of them when the
    weights fit `_WEIGHT_TILE_BYTES` (one tile — bit-identical to the
    unfused path), else the largest lane-aligned divisor that does. The
    whole [4096, 6144] int8 QKV block of a 7B model is 24 MB and cannot
    be resident at once."""
    if k_dim * row_bytes <= _WEIGHT_TILE_BYTES:
        return k_dim
    for blk in range(k_dim - k_dim % 128, 0, -128):
        if k_dim % blk == 0 and blk * row_bytes <= _WEIGHT_TILE_BYTES:
            return blk
    return k_dim


def _fused_qkv_kernel(
    *refs,
    eps: float,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    quantized: bool,
    has_bias: bool,
    n_tiles: int,
    block_in: int,
):
    it = iter(refs)
    x_ref = next(it)
    nw_ref = next(it)
    wq_ref, wk_ref, wv_ref = next(it), next(it), next(it)
    sq_ref = sk_ref = sv_ref = None
    if quantized:
        sq_ref, sk_ref, sv_ref = next(it), next(it), next(it)
    bq_ref = bk_ref = bv_ref = None
    if has_bias:
        bq_ref, bk_ref, bv_ref = next(it), next(it), next(it)
    cos_ref, sin_ref = next(it), next(it)
    q_out, k_out, v_out = next(it), next(it), next(it)
    xn_ref, qacc, kacc, vacc = next(it), next(it), next(it), next(it)

    j = pl.program_id(0) if n_tiles > 1 else 0

    @pl.when(j == 0)
    def _init():
        # rms_norm exactly as ops/basics.rms_norm: f32 accumulation,
        # output cast back to the activation dtype
        xf = x_ref[...].astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + eps)
        xn_ref[...] = (out * nw_ref[...].astype(jnp.float32)).astype(
            x_ref.dtype
        )
        qacc[...] = jnp.zeros_like(qacc)
        kacc[...] = jnp.zeros_like(kacc)
        vacc[...] = jnp.zeros_like(vacc)

    xj = xn_ref[:, pl.ds(j * block_in, block_in)]
    qacc[...] = _mm_tile(xj, wq_ref[...], qacc[...])
    kacc[...] = _mm_tile(xj, wk_ref[...], kacc[...])
    vacc[...] = _mm_tile(xj, wv_ref[...], vacc[...])

    @pl.when(j == n_tiles - 1)
    def _emit():
        dtype = x_ref.dtype
        q = _finish(
            qacc[...], sq_ref[...] if quantized else None,
            bq_ref[...] if has_bias else None, dtype,
        )
        k = _finish(
            kacc[...], sk_ref[...] if quantized else None,
            bk_ref[...] if has_bias else None, dtype,
        )
        v = _finish(
            vacc[...], sv_ref[...] if quantized else None,
            bv_ref[...] if has_bias else None, dtype,
        )
        cos = cos_ref[...].astype(jnp.float32)
        sin = sin_ref[...].astype(jnp.float32)
        q_out[...] = _rope_rotate(q, cos, sin, num_heads, head_dim, dtype)
        k_out[...] = _rope_rotate(k, cos, sin, num_kv_heads, head_dim, dtype)
        v_out[...] = v.reshape(v_out.shape)


def fused_qkv_rope(
    x: jax.Array,  # [B, hidden] residual stream
    attn_norm: jax.Array,  # [hidden]
    wq: Params, wk: Params, wv: Params,
    cos: jax.Array,  # [B, head_dim//2] f32 (positions x inv_freqs)
    sin: jax.Array,
    *,
    eps: float,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    bq: Optional[jax.Array] = None,
    bk: Optional[jax.Array] = None,
    bv: Optional[jax.Array] = None,
    block_in: Optional[int] = None,  # contraction tile; None = sized to VMEM
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """RMSNorm + QKV projections (+bias) + RoPE in ONE pallas program.

    Returns (q [B, Hq, D], k [B, Hkv, D], v [B, Hkv, D]) — exactly what
    ops/layers.qkv_head produces for non-qk-norm models, bit-identical
    when one tile covers the whole hidden dim (`_contraction_tile`)."""
    FUSED_KERNEL_ENTRIES["qkv_rope"] += 1
    B, H = x.shape
    q_dim = num_heads * head_dim
    kv_dim = num_kv_heads * head_dim
    wq_q, wq_s = _wq_parts(wq)
    wk_q, wk_s = _wq_parts(wk)
    wv_q, wv_s = _wq_parts(wv)
    blk = (
        _contraction_tile(H, (q_dim + 2 * kv_dim) * wq_q.dtype.itemsize)
        if block_in is None
        else min(block_in, H)
    )
    assert H % blk == 0, (H, blk)
    n_tiles = H // blk
    quantized = wq_s is not None
    has_bias = bq is not None

    full = lambda shape: pl.BlockSpec(shape, lambda j: (0,) * len(shape))
    wspec = lambda out: pl.BlockSpec((blk, out), lambda j: (j, 0))
    in_specs = [
        full((B, H)),  # x
        full((H,)),  # attn_norm
        wspec(q_dim), wspec(kv_dim), wspec(kv_dim),
    ]
    args = [x, attn_norm, wq_q, wk_q, wv_q]
    if quantized:
        in_specs += [full((q_dim,)), full((kv_dim,)), full((kv_dim,))]
        args += [wq_s, wk_s, wv_s]
    if has_bias:
        in_specs += [full((q_dim,)), full((kv_dim,)), full((kv_dim,))]
        args += [bq, bk, bv]
    in_specs += [full((B, head_dim // 2))] * 2
    args += [cos, sin]

    from jax.experimental.pallas import tpu as pltpu

    kernel = pl.pallas_call(
        functools.partial(
            _fused_qkv_kernel,
            eps=eps,
            num_heads=num_heads,
            num_kv_heads=num_kv_heads,
            head_dim=head_dim,
            quantized=quantized,
            has_bias=has_bias,
            n_tiles=n_tiles,
            block_in=blk,
        ),
        grid=(n_tiles,),
        in_specs=in_specs,
        out_specs=[
            full((B, num_heads, head_dim)),
            full((B, num_kv_heads, head_dim)),
            full((B, num_kv_heads, head_dim)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, num_heads, head_dim), x.dtype),
            jax.ShapeDtypeStruct((B, num_kv_heads, head_dim), x.dtype),
            jax.ShapeDtypeStruct((B, num_kv_heads, head_dim), x.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((B, H), x.dtype),  # normed activations
            pltpu.VMEM((B, q_dim), jnp.float32),
            pltpu.VMEM((B, kv_dim), jnp.float32),
            pltpu.VMEM((B, kv_dim), jnp.float32),
        ],
        interpret=interpret,
    )
    return tuple(kernel(*args))


def _fused_out_kernel(
    *refs,
    quantized: bool,
    n_tiles: int,
    block_in: int,
    partial_out: bool,
):
    it = iter(refs)
    a_ref = next(it)  # [B, q_dim] attention output (flat)
    wo_ref = next(it)  # [blk, hidden]
    so_ref = next(it) if quantized else None
    x_ref = None if partial_out else next(it)  # [B, hidden] residual input
    o_ref = next(it)  # [B, hidden]
    acc = next(it)

    j = pl.program_id(0) if n_tiles > 1 else 0

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    aj = a_ref[:, pl.ds(j * block_in, block_in)]
    acc[...] = _mm_tile(aj, wo_ref[...], acc[...])

    @pl.when(j == n_tiles - 1)
    def _emit():
        if partial_out:
            # raw f32 partial product: the meshed caller reduces across
            # the tp axis BEFORE the scale/cast/residual elementwise,
            # mirroring where GSPMD places the all-reduce
            o_ref[...] = acc[...]
        else:
            y = _finish(
                acc[...], so_ref[...] if quantized else None, None,
                x_ref.dtype,
            )
            o_ref[...] = x_ref[...] + y


def fused_attn_out_residual(
    attn: jax.Array,  # [B, q_dim] flattened attention output
    wo: Params,
    x: Optional[jax.Array] = None,  # [B, hidden] residual stream
    *,
    partial_out: bool = False,
    block_in: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Attention-output projection + residual add in ONE pallas program
    (ops/layers.attn_out for non-sandwich-norm models); bit-identical with
    a single contraction tile.

    With ``partial_out=True`` (the meshed tensor-parallel path) the kernel
    emits the RAW f32 partial product — no scale, no residual — and the
    caller psums/reduce-scatters across the tp axis before finishing.
    ``x`` is unused in that mode (the residual adds after the reduction)
    and the int8 scale, being per-output-channel, also applies after."""
    FUSED_KERNEL_ENTRIES["attn_out"] += 1
    B, q_dim = attn.shape
    wo_q, wo_s = _wq_parts(wo)
    H = wo_q.shape[1]
    blk = (
        _contraction_tile(q_dim, H * wo_q.dtype.itemsize)
        if block_in is None
        else min(block_in, q_dim)
    )
    assert q_dim % blk == 0, (q_dim, blk)
    n_tiles = q_dim // blk
    quantized = wo_s is not None and not partial_out

    full = lambda shape: pl.BlockSpec(shape, lambda j: (0,) * len(shape))
    in_specs = [
        full((B, q_dim)),
        pl.BlockSpec((blk, H), lambda j: (j, 0)),
    ]
    args = [attn, wo_q]
    if quantized:
        in_specs.append(full((H,)))
        args.append(wo_s)
    if not partial_out:
        in_specs.append(full((B, H)))
        args.append(x)
    out_dtype = jnp.float32 if partial_out else x.dtype

    from jax.experimental.pallas import tpu as pltpu

    kernel = pl.pallas_call(
        functools.partial(
            _fused_out_kernel,
            quantized=quantized,
            n_tiles=n_tiles,
            block_in=blk,
            partial_out=partial_out,
        ),
        grid=(n_tiles,),
        in_specs=in_specs,
        out_specs=full((B, H)),
        out_shape=jax.ShapeDtypeStruct((B, H), out_dtype),
        scratch_shapes=[pltpu.VMEM((B, H), jnp.float32)],
        interpret=interpret,
    )
    return kernel(*args)
