"""Ring attention: sequence/context parallelism over an `sp` mesh axis.

The reference has NO sequence parallelism (SURVEY.md §2.7: long sequences
are handled by chunked prefill + disaggregation + KV offload). On TPU we
make long-context prefill first-class instead: the prompt is sharded over
the `sp` axis of the mesh, every device computes flash attention for its
local Q chunk while K/V chunks rotate around the ring via `lax.ppermute`
(one ICI hop per step, overlapped with the chunk's attention compute by
XLA's latency-hiding scheduler). After `sp` steps every Q chunk has seen
every K/V chunk; online-softmax accumulators make the result exact.

Causality: chunk c of Q only attends chunks c' <= c of K/V; acausal pairs
are masked (the all-gather-free analogue of the blockwise causal mask).
Memory per device is O(P/sp * P/sp) per pair instead of O(P^2).

Usage (inside or outside jit):

    out = ring_prefill_attention(mesh, q, k, v, valid_len)   # global views

with q/k/v globally [P, H, D] sharded P over "sp"; or call the shard_map'd
body directly from an already-sharded computation.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -1e30


def _flash_update(
    q, k, v, m, l, acc, qpos, kpos, valid_len, scale,
    window=None, softcap=None,
):
    """One online-softmax accumulation of q-chunk against one k/v-chunk.

    q: [C, Hkv, G, D]; k/v: [C, Hkv, D]; m/l: [C, Hkv, G, 1]; acc like q.
    """
    s = jnp.einsum(
        "qhgd,khd->hgqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale  # [Hkv, G, Cq, Ck]
    if softcap is not None:  # Gemma2 logit soft-cap, pre-mask like XLA
        s = softcap * jnp.tanh(s / softcap)
    mask = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] < valid_len)
    if window is not None:  # sliding window: i sees (i-window, i]
        mask &= qpos[:, None] - kpos[None, :] < window
    s = jnp.where(mask[None, None, :, :], s, NEG_INF)
    # carry layout: [C, Hkv, G, 1] -> work in [Hkv, G, C, 1]
    m_t = jnp.transpose(m, (1, 2, 0, 3))
    l_t = jnp.transpose(l, (1, 2, 0, 3))
    m_new = jnp.maximum(m_t, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_t - m_new)
    p = jnp.exp(s - m_new)
    l_new = l_t * alpha + jnp.sum(p, axis=-1, keepdims=True)
    upd = jnp.einsum("hgqk,khd->hgqd", p, v.astype(jnp.float32))
    acc_t = jnp.transpose(acc, (1, 2, 0, 3))
    acc_new = acc_t * alpha + upd
    return (
        jnp.transpose(m_new, (2, 0, 1, 3)),
        jnp.transpose(l_new, (2, 0, 1, 3)),
        jnp.transpose(acc_new, (2, 0, 1, 3)),
    )


def ring_attention_body(
    q: jax.Array,  # [C, Hq, D] local query chunk
    k: jax.Array,  # [C, Hkv, D] local key chunk
    v: jax.Array,  # [C, Hkv, D]
    valid_len: jax.Array,  # scalar int32, GLOBAL true sequence length
    *,
    axis_name: str = "sp",
    axis_size: int,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    logit_softcap: Optional[float] = None,
) -> jax.Array:
    """SPMD body: call under shard_map with P over `axis_name`."""
    C, Hq, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    sc = float(scale) if scale is not None else 1.0 / float(D) ** 0.5
    my = lax.axis_index(axis_name)
    qpos = my * C + jnp.arange(C)

    qr = q.reshape(C, Hkv, G, D)
    m = jnp.full((C, Hkv, G, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((C, Hkv, G, 1), jnp.float32)
    acc = jnp.zeros((C, Hkv, G, D), jnp.float32)

    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    def step(i, carry):
        k_cur, v_cur, m, l, acc = carry
        # after i hops we hold the chunk originally on device (my - i)
        src = (my - i) % axis_size
        kpos = src * C + jnp.arange(C)
        # hop-level early-out: a KV chunk entirely in the future (acausal,
        # src > my) or entirely left of the sliding window (its newest key
        # is >= window behind our oldest query) contributes nothing — skip
        # the whole flash update and only keep the rotate. For Mistral-
        # class windows << P/sp most hops are skipped, so SWA ring prefill
        # compute scales with the window, not the ring length.
        needed = src <= my
        if window is not None:
            needed &= src * C + C - 1 >= my * C - (window - 1)

        def _update(_):
            return _flash_update(
                qr, k_cur, v_cur, m, l, acc, qpos, kpos, valid_len, sc,
                window=window, softcap=logit_softcap,
            )

        m, l, acc = lax.cond(needed, _update, lambda _: (m, l, acc), None)
        # rotate for the next step (the last rotate is wasted but keeps the
        # loop uniform; XLA overlaps it with the epilogue)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return k_nxt, v_nxt, m, l, acc

    _, _, m, l, acc = lax.fori_loop(0, axis_size, step, (k, v, m, l, acc))
    safe_l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / safe_l).reshape(C, Hq, D)
    # rows past valid_len are padding garbage; zero them for determinism
    out = jnp.where((qpos < valid_len)[:, None, None], out, 0.0)
    return out.astype(q.dtype)


def ring_prefill_attention(
    mesh: Mesh,
    q: jax.Array,  # [P, Hq, D] (P divisible by mesh sp size)
    k: jax.Array,  # [P, Hkv, D]
    v: jax.Array,
    valid_len: jax.Array,  # scalar int32
    *,
    axis_name: str = "sp",
    head_axis: Optional[str] = None,  # e.g. "tp" when heads are TP-sharded
    window: Optional[int] = None,  # sliding-window size; None = full
    scale: Optional[float] = None,  # score scale; None = 1/sqrt(D)
    logit_softcap: Optional[float] = None,  # gemma2 attn soft-cap
) -> jax.Array:
    """Causal self-attention with the sequence sharded over `axis_name`.

    Composes with tensor parallelism: pass head_axis="tp" and the body runs
    per (sp, tp) shard — the ring rotates K/V chunks within each tp group.
    Sliding-window layers (window set) skip the flash update on every hop
    whose KV chunk is wholly outside the window — see ring_attention_body.
    """
    sp = mesh.shape[axis_name]
    body = functools.partial(
        ring_attention_body, axis_name=axis_name, axis_size=sp,
        window=window, scale=scale, logit_softcap=logit_softcap,
    )
    spec = P(axis_name, head_axis, None)
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec, P()),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v, jnp.asarray(valid_len, jnp.int32))
