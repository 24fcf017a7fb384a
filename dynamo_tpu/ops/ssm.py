"""State-space mixer pieces (Mamba-1's selective scan and its causal
depthwise convolution) in plain XLA, float32 state and float32 arithmetic.

A sequence keeps no rows per token here: one slot of fixed size, the state
`h [d_state, d_inner]` and the convolution's tail, the last `d_conv - 1`
inputs, flat `[(d_conv - 1) * d_inner]`, oldest first. The state is stored
with `d_inner` last: on a TPU the minor dimension is tiled 128 wide, and a
minor dimension of 16 would store (and stream) eight times the values.

The recurrence, per channel d and state n:

    h_t = exp(delta_t[d] * A[n, d]) * h_{t-1} + (delta_t[d] * x_t[d]) * B_t[n]
    y_t[d] = sum_n h_t[n, d] * C_t[n]

It is computed in three forms that must agree (`tests/test_ssm_ops.py` holds
them to a plain loop): over a packed stream of several sequences, the state
zeroed at each sequence's first token and written to the sequence's slot;
over one chunk of one sequence, state carried in from its slot and out to
it; and one token for every lane of a decode batch (`_update`). The first
two run `_blocked_scan`: blocks of 32 tokens side by side, three passes of
device loops in plain XLA (no kernel that keeps the state in fast memory
yet: ROADMAP M4).

Mamba-2 (`ssd_step`, `ssd_chunk`, `ssd_packed`, at the file's end) is the
same recurrence with one scalar decay a head and `B`, `C` shared by the heads
of a group, so a head's state is a matrix `[head_dim, d_state]` and a prefill
is matrix products over chunks of tokens.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
# tokens of a block of the prefill scans (`_blocked_scan`)
SCAN_BLOCK = 32


def _update(h, x, delta, b, c, a_neg):
    """One token. h [..., N, D]; x, delta [..., D]; b, c [..., N]; a_neg
    [N, D] (negative). Returns (h_new, y [..., D])."""
    decay = jnp.exp(delta[..., None, :] * a_neg)
    h = decay * h + (delta * x)[..., None, :] * b[..., :, None]
    return h, jnp.sum(h * c[..., :, None], axis=-2)


# ------------------------------------------------------------ convolution


def conv_step(x, tail, w, bias):
    """One token a lane. x [B, D]; tail [B, (K-1)*D] (oldest first); w
    [K, D] (w[K-1] multiplies the newest input); bias [D]. Returns (conv
    output [B, D] float32, new tail)."""
    K, D = w.shape
    xf = x.astype(F32)
    acc = bias.astype(F32) + w[K - 1].astype(F32) * xf
    for k in range(K - 1):
        acc = acc + w[k].astype(F32) * tail[:, k * D:(k + 1) * D]
    new_tail = jnp.concatenate([tail[:, D:], xf], axis=-1)
    return acc, new_tail


def conv_sequence(x, prev, positions, w, bias):
    """A stream of tokens. x [T, D]; prev [K-1, D]: what stood before x[0]
    (zeros for a packed stream); positions [T]: each token's position in its
    own sequence, so that an input from before the sequence's start (another
    sequence's, in a pack) counts as zero. Returns (output [T, D] float32,
    the stream with `prev` in front [T + K - 1, D] float32)."""
    K, D = w.shape
    T = x.shape[0]
    xx = jnp.concatenate([prev.astype(F32), x.astype(F32)], axis=0)
    acc = bias.astype(F32) + w[K - 1].astype(F32) * xx[K - 1:]
    for back in range(1, K):
        shifted = xx[K - 1 - back: K - 1 - back + T]
        seen = (positions >= back)[:, None]
        acc = acc + w[K - 1 - back].astype(F32) * jnp.where(seen, shifted, 0.0)
    return acc, xx


def tail_after(xx, count, K):
    """The tail a sequence leaves after `count` tokens of the stream that
    `conv_sequence` returned (`prev` in front): its last K - 1 inputs, flat."""
    D = xx.shape[1]
    return lax.dynamic_slice(xx, (count, 0), (K - 1, D)).reshape(-1)


def packed_tails(x, positions, last_idx, K):
    """[N, (K-1)*D]: the tail each packed sequence leaves; `last_idx` [N]
    indexes its last token. Inputs from before its start are zeros."""
    T, D = x.shape
    back = jnp.arange(K - 2, -1, -1, dtype=jnp.int32)  # oldest first
    idx = last_idx[:, None] - back[None, :]  # [N, K-1]
    seen = positions[last_idx][:, None] >= back[None, :]
    rows = x.astype(F32)[jnp.clip(idx, 0, T - 1)]  # [N, K-1, D]
    return jnp.where(seen[..., None], rows, 0.0).reshape(idx.shape[0], -1)


# ------------------------------------------------------------------- scan


def scan_step(h, x, delta, b, c, a_neg, live):
    """One token for every lane of a decode batch. h [B, N, D]; x, delta
    [B, D]; b, c [B, N]; live [B] bool: a lane that holds no decoding
    sequence keeps its slot as it is (a sequence in the middle of a chunked
    prefill may own it). Returns (h_new, y [B, D])."""
    new, y = _update(h, x, delta, b, c, a_neg)
    return jnp.where(live[:, None, None], new, h), y


def _block_len(T: int) -> int:
    """The largest divisor of T that is at most SCAN_BLOCK."""
    return next(n for n in range(min(SCAN_BLOCK, T), 0, -1) if T % n == 0)


def _blocked_scan(h0, x, delta, b, c, a_neg, keep, capture=None):
    """The recurrence over T tokens in blocks of L, the blocks side by side.

    A loop over tokens one at a time pays a device loop's overhead 512
    times a layer and moves 327 KB in each; here a loop's iteration holds
    one token of every block, T / L of them. Three passes, the same
    arithmetic reassociated (`h_t = P h_in + e`, P the product of a block's
    decays): (1) every block from a zero state, L steps, keeping its last
    state `e` and its decays' product `p`; (2) over the blocks, the state
    each starts from: `h_in[g+1] = p[g] h_in[g] + e[g]`; (3) every block
    again from its true start, L steps, which gives y and every token's
    state.

    h0 [N, D]; x, delta [T, D] (delta 0 where a token must leave the state
    as it is: its decay is then 1 and its input 0); b, c [T, N]; keep [T]
    float32, 0 where the state is zeroed before the token (a sequence's
    first in a pack), else 1. `capture`: (states [S, N, D], slots [T]): the
    state behind token t is written to `states[slots[t]]` (the null slot
    for a token that is not its sequence's last). Returns (y [T, D], the
    state behind the last token, states or None)."""
    T, D = x.shape
    N = a_neg.shape[0]
    L = _block_len(T)
    G = T // L

    def blocks(v):  # [T, ...] -> [L, G, ...]: step j holds token j of every block
        return jnp.swapaxes(v.reshape((G, L) + v.shape[1:]), 0, 1)

    def decay_and_input(x_t, d_t, b_t, k_t):
        a = jnp.exp(d_t[:, None, :] * a_neg) * k_t[:, None, None]
        return a, (d_t * x_t)[:, None, :] * b_t[:, :, None]

    def local(carry, inp):
        e, p = carry
        a, u = decay_and_input(*inp)
        return (a * e + u, a * p), None

    front = (blocks(x), blocks(delta), blocks(b), blocks(keep))
    (e, p), _ = lax.scan(
        local, (jnp.zeros((G, N, D), F32), jnp.ones((G, N, D), F32)), front
    )

    def across(h, ep):
        return ep[1] * h + ep[0], h

    h_last, h_in = lax.scan(across, h0.astype(F32), (e, p))

    def exact(carry, inp):
        h, states = carry
        x_t, d_t, b_t, k_t, c_t, slots_t = inp
        a, u = decay_and_input(x_t, d_t, b_t, k_t)
        h = a * h + u
        if states is not None:
            states = states.at[slots_t].set(h)
        return (h, states), jnp.sum(h * c_t[:, :, None], axis=1)

    states, slots = capture if capture is not None else (None, jnp.zeros((T,), jnp.int32))
    (_, states), y = lax.scan(
        exact, (h_in, states), front + (blocks(c), blocks(slots))
    )
    return jnp.swapaxes(y, 0, 1).reshape(T, D), h_last, states


def scan_chunk(h0, x, delta, b, c, a_neg, valid):
    """One chunk of one sequence. h0 [N, D]: the state carried in; x, delta
    [T, D]; b, c [T, N]; valid [T] bool: a padded token leaves the state as
    it is. Returns (y [T, D], the state carried out)."""
    delta = jnp.where(valid[:, None], delta, 0.0)
    y, h, _ = _blocked_scan(
        h0, x, delta, b, c, a_neg, jnp.ones(valid.shape, F32)
    )
    return y, h


def scan_packed(states, x, delta, b, c, a_neg, positions, valid, write_slots):
    """Several sequences back to back. states [S, N, D]: every lane's slot;
    positions [T]: a token at position 0 starts from a zero state; valid
    [T] bool (padding is not); write_slots [T]: its sequence's slot for a
    sequence's last token, the null slot (whose content nobody reads) for
    every other. Returns (y [T, D], states)."""
    N, D = a_neg.shape
    delta = jnp.where(valid[:, None], delta, 0.0)
    y, _, states = _blocked_scan(
        jnp.zeros((N, D), F32), x, delta, b, c, a_neg,
        (positions != 0).astype(F32), capture=(states, write_slots),
    )
    return y, states


# ---------------------------------------------------------------- Mamba-2
#
# Per head j of H (each `P` channels wide), group g = j // (H / G):
#
#     S_t[j] = exp(dt_t[j] * a[j]) * S_{t-1}[j] + (dt_t[j] * x_t[j]) outer B_t[g]
#     y_t[j] = S_t[j] C_t[g]
#
# with `a = -exp(A_log)` a scalar a head and S[j] `[P, N]` float32, `d_state`
# last (the TPU's tiled dimension). A lane's slot is `[H, P, N]`. On the chip
# a decode step's update is `ops/pallas_ssm.py`'s kernel over the live lanes'
# rows; `ssd_step` is the form it is held to, and what runs everywhere else.


def ssd_step(s, x, dt, a, b, c, live):
    """One token for every lane. s [B, H, P, N]; x [B, H, P]; dt [B, H]
    (behind its softplus); a [H] (negative); b, c [B, G, N]; live [B] bool:
    a lane that holds no decoding sequence keeps its slot as it is. Returns
    (s_new, y [B, H, P])."""
    B, H, P, N = s.shape
    G = b.shape[1]
    by_group = lambda v: v.reshape((B, G, H // G) + v.shape[2:])
    s5, dt5 = by_group(s), by_group(dt)
    decay = jnp.exp(dt5 * a.reshape(G, H // G))
    new = (
        decay[..., None, None] * s5
        + (dt5[..., None] * by_group(x))[..., None] * b[:, :, None, None, :]
    )
    y = jnp.sum(new * c[:, :, None, None, :], axis=-1)
    new = jnp.where(live[:, None, None, None], new.reshape(s.shape), s)
    return new, y.reshape(B, H, P)


def _ssd(h0, x, dt, a, b, c, resets, chunk):
    """The recurrence over T tokens in chunks of `chunk`: within a chunk by
    products against the lower-triangular decay, between chunks by the
    carried state (Mamba-2's own algorithm), float32 at the highest matmul
    precision.

    h0 [H, P, N]; x [T, H, P]; dt [T, H], 0 where a token must leave the
    state as it is (its decay is then 1 and its input 0); a [H]; b, c
    [T, G, N]; resets [T] bool: the state is zeroed before the token (a
    sequence's first in a pack). A token sees an earlier one, or the carried
    state, only where no reset lies between them. The decay between two
    tokens is the exponential of a difference of float32 sums counted from
    the chunk's start, so it carries the sums' rounding: a relative 6e-8
    times the sum, 4e-5 where a head forgets at 5 a token over a chunk of 128
    (the fastest the published constants give), against 4e-3 a bfloat16
    state would carry. Returns (y [T, H, P], the state behind the last token
    [H, P, N], `behind`: token index -> the state behind that token)."""
    T, H, P = x.shape
    G, N = b.shape[1:]
    R, Q = H // G, chunk
    pad = (-T) % Q
    if pad:
        x, dt, b, c, resets = (
            jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
            for v in (x, dt, b, c, resets)
        )
    nc = (T + pad) // Q
    hi = lax.Precision.HIGHEST
    # [nc, G, R, Q(, P)]: a head's chunk is a row of Q values, the products
    # below are batched over (chunk, group[, head of the group])
    heads = lambda v: jnp.moveaxis(v.reshape((nc, Q, G, R) + v.shape[2:]), 1, 3)
    cum = jnp.cumsum(heads(dt * a), axis=3)  # log of the decay since the chunk's start
    xd = heads(x * dt[..., None])
    bq = jnp.swapaxes(b.reshape(nc, Q, G, N), 1, 2)  # [nc, G, Q, N]
    cq = jnp.swapaxes(c.reshape(nc, Q, G, N), 1, 2)
    r = jnp.cumsum(resets.astype(jnp.int32)).reshape(nc, Q)  # resets so far
    r_in = jnp.concatenate([jnp.zeros((1,), jnp.int32), r[:-1, -1]])
    at = jnp.arange(Q)
    sees = (r[:, :, None] == r[:, None, :]) & (at[None, :, None] >= at[None, None, :])
    decay = jnp.exp(jnp.where(
        sees[:, None, None], cum[..., :, None] - cum[..., None, :], -jnp.inf
    ))  # [nc, G, R, Q, Q]
    cb = jnp.einsum("cgin,cgjn->cgij", cq, bq, precision=hi)
    y = jnp.einsum("cgrij,cgrjp->cgrip", cb[:, :, None] * decay, xd, precision=hi)
    # what each chunk adds to the state behind its last token, and what it
    # lets through of the state it was handed
    to_end = jnp.exp(jnp.where(
        (r == r[:, -1:])[:, None, None], cum[..., -1:] - cum, -jnp.inf
    ))
    added = jnp.einsum("cgrjp,cgjn->cgrpn", to_end[..., None] * xd, bq, precision=hi)
    through = jnp.exp(cum[..., -1]) * (r[:, -1] == r_in)[:, None, None]

    def across(h, chunk_):
        add, keep = chunk_
        return keep[..., None, None] * h + add, h

    h_last, h_in = lax.scan(
        across, h0.astype(F32).reshape(G, R, P, N), (added, through)
    )
    from_in = jnp.exp(cum) * (r == r_in[:, None])[:, None, None]
    y = y + from_in[..., None] * jnp.einsum(
        "cgin,cgrpn->cgrip", cq, h_in, precision=hi
    )
    y = jnp.moveaxis(y, 3, 1).reshape(nc * Q, H, P)[:T]

    def behind(t):
        ci, i = t // Q, t % Q
        cum_c, r_c = cum[ci], r[ci]
        seen = (at <= i) & (r_c == r_c[i])
        w = jnp.exp(jnp.where(seen, cum_c[..., i, None] - cum_c, -jnp.inf))
        local = jnp.einsum("grjp,gjn->grpn", w[..., None] * xd[ci], bq[ci], precision=hi)
        carried = jnp.exp(cum_c[..., i]) * (r_c[i] == r_in[ci])
        return (local + carried[..., None, None] * h_in[ci]).reshape(H, P, N)

    return y, h_last.reshape(H, P, N), behind


def ssd_chunk(h0, x, dt, a, b, c, valid, chunk):
    """One chunk of one sequence's prompt. h0 [H, P, N]: the state carried
    in; valid [T] bool: a padded token leaves the state as it is. Returns
    (y [T, H, P], the state carried out)."""
    dt = jnp.where(valid[:, None], dt, 0.0)
    y, h, _ = _ssd(h0, x, dt, a, b, c, jnp.zeros(valid.shape, bool), chunk)
    return y, h


def ssd_packed(states, x, dt, a, b, c, positions, valid, last_idx, seg_slots, count, chunk):
    """Several sequences back to back. states [S, H, P, N]: every lane's
    slot; positions [T]: a token at position 0 starts from a zero state;
    valid [T] bool (padding is not); last_idx [n]: each sequence's last
    token; seg_slots [n]: its slot; count (scalar): how many of the n
    segments hold a sequence (the first `count`; no state is computed for
    the others). Returns (y [T, H, P], states)."""
    dt = jnp.where(valid[:, None], dt, 0.0)
    y, _, behind = _ssd(
        jnp.zeros(states.shape[1:], F32), x, dt, a, b, c,
        valid & (positions == 0), chunk,
    )

    def keep(n, states):
        return states.at[seg_slots[n]].set(behind(last_idx[n]))

    return y, lax.fori_loop(0, count, keep, states)
