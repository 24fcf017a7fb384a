"""Int8-resident paged KV cache: device-side quantized storage helpers.

Promotes the PR-4 wire codec (disagg/protocols.kv_quantize_int8 — symmetric
absmax int8 with one f32 scale per (layer, head, block)) from wire-only to
DEVICE-resident: the paged KV cache itself stores int8 mantissas plus a
per-block scale plane, so every decode step reads ~half the KV bytes from
HBM and dequantizes inside the attention kernel (pallas) or right after the
gather (XLA path). bf16 K/V for past tokens never materializes in HBM.

Layout: the cache is a tuple of one container per layer (each its own
device buffer, so a step program writes a layer in place and never slices
or re-assembles a pool); a layer's container is a plain array, or for the
int8-resident cache a plain dict, so it rides every jit/donate/pytree path
unchanged:

    cache[i] = {"q": int8 [Hkv, num_blocks, bs, D],
                "s": f32  [Hkv, num_blocks]}

The scale scheme is EXACTLY the wire codec's (amax/127 per block, inv=0 for
all-zero blocks), so int8-resident blocks ship verbatim over disagg frames,
peer pulls, and the G2/G3 offload tiers — no recode, no double quantization.

Write semantics:

  * whole-block writes (prefill / chunked prefill) compute the exact
    per-block absmax — bit-identical to the numpy wire codec run on the
    same values;
  * append writes (decode / spec-verify) grow the block's scale
    monotonically: new_scale = max(old_scale, token_absmax/127). When the
    scale grows, the block's existing mantissas are rescaled
    (round(q * old/new)) in the same fused scatter — old tokens lose at
    most 1/2 ulp per growth event, bounded by the absmax-of-block-so-far
    scheme. A write at block offset 0 RESETS the scale (recycled blocks
    carry a dead occupant's scale; attention masks its slots by position,
    but its scale must not inflate the fresh block's quantization range).
"""

from __future__ import annotations

from typing import Union

import jax
import jax.numpy as jnp

KVLayer = Union[jax.Array, dict]  # one layer: [Hkv, nb, bs, D] or {"q","s"}
KVCache = tuple  # one KVLayer per model layer


def make_cache(
    num_layers: int, layer_shape: tuple[int, ...], dtype, *, quantized: bool
) -> KVCache:
    """Zero-initialized cache: per layer a plain [Hkv, nb, bs, D] array, or
    the int8+scale container."""

    def layer() -> KVLayer:
        if not quantized:
            return jnp.zeros(layer_shape, dtype)
        return {
            "q": jnp.zeros(layer_shape, jnp.int8),
            "s": jnp.zeros(layer_shape[:-2], jnp.float32),
        }

    return tuple(layer() for _ in range(num_layers))


def cache_nbytes(cache: KVCache) -> int:
    return sum(
        a.size * a.dtype.itemsize
        for a in jax.tree_util.tree_leaves(cache)
    )


def cache_sharding(kv_sharding, num_layers: int, quantized: bool):
    """Sharding pytree matching the cache container, from one layer's
    [Hkv, nb, bs, D] sharding: the scale plane [Hkv, nb] inherits the
    layer's leading two axes (the head axis is what TP shards)."""
    if kv_sharding is None:
        return None
    layer = kv_sharding
    if quantized:
        from jax.sharding import NamedSharding, PartitionSpec

        sspec = PartitionSpec(*tuple(kv_sharding.spec)[:2])
        layer = {
            "q": kv_sharding,
            "s": NamedSharding(kv_sharding.mesh, sspec),
        }
    return (layer,) * num_layers


# ------------------------------------------------------------ quant math
#
# Mirrors disagg/protocols.kv_quantize_int8 exactly (scale = amax/127,
# inv = 1/scale where scale > 0 else 0, round-half-to-even, clip +-127) so
# device-quantized blocks and wire-quantized blocks are interchangeable.


def block_scale(amax: jax.Array) -> jax.Array:
    return (amax / 127.0).astype(jnp.float32)


def scale_inv(scale: jax.Array) -> jax.Array:
    return jnp.where(scale > 0, 1.0 / jnp.maximum(scale, 1e-30), 0.0)


def quantize_with(x: jax.Array, inv: jax.Array) -> jax.Array:
    """Quantize f32 values with a broadcastable inverse scale."""
    return jnp.clip(jnp.round(x * inv), -127, 127).astype(jnp.int8)


def dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    """int8 mantissas [..., bs, D] * per-block scale [...] -> f32."""
    return q.astype(jnp.float32) * scale[..., None, None]


def dequantize_layer(layer: dict) -> jax.Array:
    """Whole-layer f32 view (XLA fallback paths that need dense K/V)."""
    return dequantize(layer["q"], layer["s"])


# ---------------------------------------------------------------- writes


def scatter_token_rows(
    pages: jax.Array,  # [Hkv, nb, bs, D]
    new: jax.Array,  # [T, Hkv, D]
    slot_indices: jax.Array,  # [T] int32 flat slots (block*bs + offset)
) -> jax.Array:
    """Write token t's head h into row h*N + slot[t] of the pages seen as
    [Hkv*N, D] rows (N = nb*bs).

    The form matters, not only the result: with the indexed dimension
    major, XLA scatters into the donated buffer where it lies; with slots
    indexed behind the head axis (`flat.at[:, slots]`) it re-lays the whole
    layer before and after every write (PERF.md section 6, PR 26).

    Who still calls it (through `ops.attention.write_decode_kv`): the
    programs that write many tokens a lane or run no decode kernel (packed
    and chunked prefill, the verify window, `parallel/pipeline.py`), the
    int8-resident append below, and a decode step whose attention is the
    XLA form. A decode step through the paged kernel does not: XLA writes
    the `T x Hkv` rows one after another, 2 ms of a 14 ms step at 64 lanes
    and 8 KV heads in 32 layers, so the kernel appends the row itself
    (`ops.attention.decode_append_attention`; PERF.md section 6, PR 47)."""
    Hkv, nb, bs, D = pages.shape
    N = nb * bs
    rows = (
        jnp.arange(Hkv, dtype=jnp.int32)[None, :] * N
        + slot_indices.astype(jnp.int32)[:, None]
    ).reshape(-1)  # [T*Hkv], token-major like new.reshape(-1, D)
    flat = pages.reshape(Hkv * N, D).at[rows].set(
        new.reshape(-1, D).astype(pages.dtype)
    )
    return flat.reshape(Hkv, nb, bs, D)


def quantize_blocks(blocks: jax.Array) -> dict:
    """Whole blocks [..., bs, D] -> {"q": int8 mantissas, "s": f32 [...]}
    with each block's exact absmax scale (the wire codec's scheme)."""
    xf = blocks.astype(jnp.float32)
    scale = block_scale(jnp.max(jnp.abs(xf), axis=(-2, -1)))
    return {
        "q": quantize_with(xf, scale_inv(scale)[..., None, None]),
        "s": scale,
    }


def write_blocks_quant(
    layer: dict,  # {"q": [Hkv, nb, bs, D] int8, "s": [Hkv, nb] f32}
    k_blocks: jax.Array,  # [Hkv, n, bs, D] logical-dtype new blocks
    block_table: jax.Array,  # [n] int32
) -> dict:
    """Whole-block write (prefill/chunk): exact per-block absmax scales."""
    new = quantize_blocks(k_blocks)
    return {
        "q": layer["q"].at[:, block_table].set(new["q"]),
        "s": layer["s"].at[:, block_table].set(new["s"]),
    }


def write_tokens_quant(
    layer: dict,  # {"q": [Hkv, nb, bs, D] int8, "s": [Hkv, nb] f32}
    new: jax.Array,  # [T, Hkv, D] logical-dtype tokens
    slot_indices: jax.Array,  # [T] int32 flat slots (block*bs + offset)
) -> dict:
    """Append-token write (decode / spec-verify / packed prefill).

    Handles any number of tokens landing in the same block in one call
    (verify windows, packed segments): incoming per-block maxima are
    combined with a scatter-max, existing mantissas of every touched block
    are rescaled once, then the tokens scatter by flat slot. A token at
    block offset 0 marks the block fresh — the previous occupant's scale
    is discarded, not grown over.
    """
    q_cache, s = layer["q"], layer["s"]
    Hkv, nb, bs, D = q_cache.shape
    bids = slot_indices // bs  # [T]
    offs = slot_indices % bs
    xf = new.astype(jnp.float32).transpose(1, 0, 2)  # [Hkv, T, D]
    tok_amax = jnp.max(jnp.abs(xf), axis=-1)  # [Hkv, T]

    # per-block incoming absmax + touched/fresh masks (duplicate-safe)
    inc = jnp.zeros((Hkv, nb), jnp.float32).at[:, bids].max(tok_amax)
    touched = jnp.zeros((nb,), bool).at[bids].set(True)
    fresh = (
        jnp.zeros((nb,), jnp.int32)
        .at[bids]
        .max((offs == 0).astype(jnp.int32))
    ) > 0

    base = jnp.where(fresh[None, :], 0.0, s)  # scale kept from old content
    new_s = jnp.where(
        touched[None, :], jnp.maximum(base, block_scale(inc)), s
    )

    # rescale existing mantissas of touched blocks (gather/scatter only
    # the T referenced blocks; duplicates gather+scatter identical data)
    old_g = q_cache[:, bids]  # [Hkv, T, bs, D]
    inv_g = scale_inv(new_s)[:, bids]  # [Hkv, T]
    ratio = (base[:, bids] * inv_g)[..., None, None]
    resc = jnp.clip(
        jnp.round(old_g.astype(jnp.float32) * ratio), -127, 127
    ).astype(jnp.int8)
    q_cache = q_cache.at[:, bids].set(resc)

    # insert the new tokens quantized by their block's (possibly grown)
    # scale, via the row scatter the bf16 path uses
    tok_q = quantize_with(xf, inv_g[..., None])  # [Hkv, T, D]
    q_cache = scatter_token_rows(
        q_cache, tok_q.transpose(1, 0, 2), slot_indices
    )
    return {"q": q_cache, "s": new_s}
