"""`ops/ssm.py`: the three forms of the selective scan (packed with resets,
chunked with carry, single step) and the convolution with a carried tail add
up to one plain loop over tokens, written here in numpy float64.

Tolerance: the prefill forms run the recurrence in blocks (a block's decays
multiplied up, then applied to the state it starts from), the same float32
arithmetic reassociated, so they differ from each other and from the
float64 loop by float32 rounding over the sequence: 1e-5 absolute on values of order
1 is ten times what 40 tokens read (1e-6) and far under what a wrong form
gives (a missed reset or a tail from the wrong place reads 1e-1). The
Mamba-2 forms (`ssd_packed`, `ssd_chunk`, `ssd_step`) are held to their own
loop the same way, at several sizes of the recurrence's own chunk.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops import ssm

N, D, K = 4, 24, 4
TOL = 1e-5


def inputs(T: int, seed: int):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((T, D)).astype(np.float32)
    delta = np.log1p(np.exp(rng.standard_normal((T, D)) - 2)).astype(np.float32)
    b = rng.standard_normal((T, N)).astype(np.float32)
    c = rng.standard_normal((T, N)).astype(np.float32)
    return x, delta, b, c


A_NEG = -np.exp(np.log(np.arange(1, N + 1, dtype=np.float64))[:, None] * np.ones((1, D)))


def loop(x, delta, b, c, h=None):
    """The plain loop, float64: (y [T, D], the state after every token)."""
    h = np.zeros((N, D)) if h is None else h.astype(np.float64)
    ys, hs = [], []
    for t in range(x.shape[0]):
        h = np.exp(delta[t][None, :] * A_NEG) * h + (delta[t] * x[t])[None, :] * b[t][:, None]
        ys.append((h * c[t][:, None]).sum(0))
        hs.append(h.copy())
    return np.stack(ys), np.stack(hs)


def conv_loop(x, w, bias):
    T = x.shape[0]
    padded = np.concatenate([np.zeros((K - 1, D)), x.astype(np.float64)])
    return np.stack([bias + sum(w[k] * padded[t + k] for k in range(K)) for t in range(T)])


def test_three_scan_forms_add_up_to_the_plain_loop():
    """A 29-token sequence: whole in a pack beside a 7-token neighbour;
    as chunks of 16 and 13 (the second padded to 16) from a carried state;
    and its last 5 tokens as single steps from the chunks' state, in a batch
    with a lane that must keep its slot."""
    T, T2 = 29, 7
    x, delta, b, c = inputs(T, 1)
    x2, delta2, b2, c2 = inputs(T2, 2)
    want_y, want_h = loop(x, delta, b, c)
    want_y2, want_h2 = loop(x2, delta2, b2, c2)
    a_neg = jnp.asarray(A_NEG, jnp.float32)
    # packed: the neighbour first, then the sequence, then 4 padding tokens
    P = T2 + T + 4
    cat = lambda u, v, w: jnp.asarray(np.concatenate([u, v, np.zeros((4,) + u.shape[1:], np.float32)]))
    positions = jnp.asarray(np.concatenate([np.arange(T2), np.arange(T), np.zeros(4)]).astype(np.int32))
    # a sequence's last token writes its slot; every other the null slot (3)
    slots = np.full(P, 3, np.int32)
    slots[T2 - 1], slots[T2 + T - 1] = 2, 0
    valid = jnp.asarray(np.arange(P) < T2 + T)
    states = jnp.full((4, N, D), 9.0, jnp.float32)
    y, states = jax.jit(ssm.scan_packed)(
        states, cat(x2, x, 0), cat(delta2, delta, 0), cat(b2, b, 0), cat(c2, c, 0),
        a_neg, positions, valid, jnp.asarray(slots),
    )
    np.testing.assert_allclose(np.asarray(y[:T2]), want_y2, atol=TOL)
    np.testing.assert_allclose(np.asarray(y[T2:T2 + T]), want_y, atol=TOL)
    np.testing.assert_allclose(np.asarray(states[0]), want_h[-1], atol=TOL)
    np.testing.assert_allclose(np.asarray(states[2]), want_h2[-1], atol=TOL)
    assert np.all(np.asarray(states[1]) == 9.0)  # nobody's slot: untouched
    # chunked: 16 tokens from zero, then 13 and 3 of padding from the carry
    C = 16
    pad = lambda u: jnp.asarray(np.concatenate([u[C:], np.ones((2 * C - T,) + u.shape[1:], np.float32)]))
    chunk = jax.jit(ssm.scan_chunk)
    y1, h1 = chunk(jnp.zeros((N, D)), *(jnp.asarray(u[:C]) for u in (x, delta, b, c)), a_neg, jnp.ones(C, bool))
    y2, h2 = chunk(h1, pad(x), pad(delta), pad(b), pad(c), a_neg, jnp.arange(C) < T - C)
    np.testing.assert_allclose(np.asarray(y1), want_y[:C], atol=TOL)
    np.testing.assert_allclose(np.asarray(y2[: T - C]), want_y[C:], atol=TOL)
    np.testing.assert_allclose(np.asarray(h1), want_h[C - 1], atol=TOL)
    np.testing.assert_allclose(np.asarray(h2), want_h[-1], atol=TOL)
    # the chunked and the packed forms block the tokens differently (40
    # tokens in blocks of 20, 16 in one of 16), so they agree to float32
    # rounding, not to the bit
    np.testing.assert_allclose(np.asarray(h2), np.asarray(states[0]), atol=TOL)
    # single steps: the last 5 tokens from the state 24 tokens leave
    h = jnp.stack([jnp.asarray(want_h[T - 6], jnp.float32), jnp.full((N, D), 5.0)])
    step = jax.jit(ssm.scan_step)
    live = jnp.asarray([True, False])
    for t in range(T - 5, T):
        two = lambda u: jnp.stack([jnp.asarray(u[t]), jnp.asarray(u[t])])
        h, y = step(h, two(x), two(delta), two(b), two(c), a_neg, live)
        np.testing.assert_allclose(np.asarray(y[0]), want_y[t], atol=TOL)
    np.testing.assert_allclose(np.asarray(h[0]), want_h[-1], atol=TOL)
    assert np.all(np.asarray(h[1]) == 5.0)


@pytest.mark.parametrize("count", [1, 2, 9])
def test_convolution_with_a_carried_tail_equals_one_pass(count):
    """A 20-token stream through the convolution whole; as a first chunk of
    `count` tokens (fewer than the taps, too) and the rest from its tail;
    and token by token through `conv_step`; and two streams in one pack."""
    rng = np.random.default_rng(3)
    T = 20
    x = rng.standard_normal((T, D)).astype(np.float32)
    w = rng.standard_normal((K, D)).astype(np.float32)
    bias = rng.standard_normal(D).astype(np.float32)
    want = conv_loop(x, w, bias)
    zeros = jnp.zeros((K - 1, D))
    whole, stream = ssm.conv_sequence(jnp.asarray(x), zeros, jnp.arange(T), jnp.asarray(w), jnp.asarray(bias))
    np.testing.assert_allclose(np.asarray(whole), want, atol=TOL)
    # a chunk of 12 of which `count` are real, then the rest from its tail
    first = np.concatenate([x[:count], np.full((12 - count, D), 7.0, np.float32)])
    out1, s1 = ssm.conv_sequence(jnp.asarray(first), zeros, jnp.arange(12), jnp.asarray(w), jnp.asarray(bias))
    tail = ssm.tail_after(s1, count, K)
    np.testing.assert_allclose(np.asarray(out1[:count]), want[:count], atol=TOL)
    out2, s2 = ssm.conv_sequence(
        jnp.asarray(x[count:]), tail.reshape(K - 1, D), count + jnp.arange(T - count),
        jnp.asarray(w), jnp.asarray(bias),
    )
    np.testing.assert_allclose(np.asarray(out2), want[count:], atol=TOL)
    np.testing.assert_array_equal(
        np.asarray(ssm.tail_after(s2, T - count, K)), np.asarray(ssm.tail_after(stream, T, K))
    )
    # token by token from an empty tail
    t_ = jnp.zeros((1, (K - 1) * D))
    for t in range(T):
        out, t_ = ssm.conv_step(jnp.asarray(x[t][None]), t_, jnp.asarray(w), jnp.asarray(bias))
        np.testing.assert_allclose(np.asarray(out[0]), want[t], atol=TOL)
    np.testing.assert_array_equal(np.asarray(t_[0]), np.asarray(ssm.tail_after(stream, T, K)))
    # two streams in one pack: the second sees nothing of the first
    both = jnp.asarray(np.concatenate([x[:count], x]))
    positions = jnp.asarray(np.concatenate([np.arange(count), np.arange(T)]).astype(np.int32))
    packed, _ = ssm.conv_sequence(both, zeros, positions, jnp.asarray(w), jnp.asarray(bias))
    np.testing.assert_allclose(np.asarray(packed[count:]), want, atol=TOL)
    tails = ssm.packed_tails(both, positions, jnp.asarray([count - 1, count + T - 1]), K)
    np.testing.assert_array_equal(np.asarray(tails[1]), np.asarray(ssm.tail_after(stream, T, K)))
    np.testing.assert_array_equal(np.asarray(tails[0]), np.asarray(tail))


# ---------------------------------------------------------------- Mamba-2

H2, P2, G2, N2 = 6, 5, 2, 4  # heads, head width, groups of B and C, state


def inputs2(T: int, seed: int):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((T, H2, P2)).astype(np.float32)
    # steps from gentle (a decay of 0.98 a token) to one that forgets a state
    # in a token (1e-9 at the fastest head)
    dt = np.log1p(np.exp(rng.standard_normal((T, H2)) - 2)).astype(np.float32)
    b = rng.standard_normal((T, G2, N2)).astype(np.float32)
    c = rng.standard_normal((T, G2, N2)).astype(np.float32)
    return x, dt, b, c


A2 = -np.linspace(1.0, 16.0, H2)


def loop2(x, dt, b, c, s=None):
    """The plain loop over positions, float64: head j reads group j // 3.
    Returns (y [T, H, P], the state after every token [T, H, P, N])."""
    s = np.zeros((H2, P2, N2)) if s is None else s.astype(np.float64)
    ys, ss = [], []
    for t in range(x.shape[0]):
        bh = np.repeat(b[t].astype(np.float64), H2 // G2, axis=0)
        ch = np.repeat(c[t].astype(np.float64), H2 // G2, axis=0)
        s = (
            np.exp(dt[t] * A2)[:, None, None] * s
            + (dt[t][:, None] * x[t])[:, :, None] * bh[:, None, :]
        )
        ys.append((s * ch[:, None, :]).sum(-1))
        ss.append(s.copy())
    return np.stack(ys), np.stack(ss)


@pytest.mark.parametrize("chunk", [4, 8, 64])
def test_three_mamba2_forms_add_up_to_the_plain_loop(chunk):
    """A 29-token sequence: whole in a pack between a 7-token and a 3-token
    neighbour (the boundaries fall inside the recurrence's own chunks, the
    last sequence ends the pack's valid part); as chunks of 16 and 13 (the
    second padded to 16) from a carried state; and its last 5 tokens as
    single steps from the chunks' state, in a batch with a lane that must
    keep its slot. At a chunk of 4 and of 8 (several of the recurrence's
    chunks a program) and of 64 (one, padded)."""
    T, T0, T2 = 29, 7, 3
    x, dt, b, c = inputs2(T, 1)
    x0, dt0, b0, c0 = inputs2(T0, 2)
    x2, dt2, b2, c2 = inputs2(T2, 3)
    want_y, want_s = loop2(x, dt, b, c)
    a = jnp.asarray(A2, jnp.float32)

    P = 48  # the pack: 7 + 29 + 3 tokens and 9 of padding
    pad = P - T0 - T - T2
    cat = lambda u, v, w: jnp.asarray(
        np.concatenate([u, v, w, np.zeros((pad,) + v.shape[1:], np.float32)]))
    positions = jnp.asarray(np.concatenate(
        [np.arange(T0), np.arange(T), np.arange(T2), np.zeros(pad)]).astype(np.int32))
    valid = jnp.arange(P) < T0 + T + T2
    states = jnp.full((5, H2, P2, N2), 7.0, jnp.float32)  # dirty slots; 4 is the null one
    last_idx = jnp.asarray([T0 - 1, T0 + T - 1, T0 + T + T2 - 1, 0], jnp.int32)
    seg_slots = jnp.asarray([3, 1, 0, 4], jnp.int32)
    y, states = jax.jit(ssm.ssd_packed, static_argnums=11)(
        states, cat(x0, x, x2), cat(dt0, dt, dt2), a, cat(b0, b, b2), cat(c0, c, c2),
        positions, valid, last_idx, seg_slots, jnp.int32(3), chunk,
    )
    np.testing.assert_allclose(np.asarray(y)[T0:T0 + T], want_y, atol=TOL)
    np.testing.assert_allclose(np.asarray(states[1]), want_s[-1], atol=TOL)
    np.testing.assert_allclose(np.asarray(states[3]), loop2(x0, dt0, b0, c0)[1][-1], atol=TOL)
    np.testing.assert_allclose(np.asarray(states[0]), loop2(x2, dt2, b2, c2)[1][-1], atol=TOL)
    # no state is computed for the fourth segment, which holds no sequence
    assert np.all(np.asarray(states[2]) == 7.0) and np.all(np.asarray(states[4]) == 7.0)

    C = 16
    chunked = jax.jit(ssm.ssd_chunk, static_argnums=7)
    h = jnp.zeros((H2, P2, N2), jnp.float32)
    ys = []
    for start in (0, C):
        n = min(C, T - start)
        grow = lambda v: jnp.asarray(
            np.concatenate([v[start:start + n], np.ones((C - n,) + v.shape[1:], np.float32)]))
        yc, h = chunked(h, grow(x), grow(dt), a, grow(b), grow(c), jnp.arange(C) < n, chunk)
        ys.append(np.asarray(yc)[:n])
    np.testing.assert_allclose(np.concatenate(ys), want_y, atol=TOL)
    np.testing.assert_allclose(np.asarray(h), want_s[-1], atol=TOL)

    # single steps: lane 0 continues the sequence from the state behind token
    # 23, lane 1 holds no decoding sequence and keeps what its slot holds
    s = jnp.stack([jnp.asarray(want_s[23], jnp.float32), jnp.full((H2, P2, N2), 3.0)])
    step = jax.jit(ssm.ssd_step)
    for t in range(24, T):
        two = lambda v: jnp.stack([jnp.asarray(v[t]), jnp.asarray(v[t])])
        s, y1 = step(s, two(x), two(dt), a, two(b), two(c), jnp.asarray([True, False]))
        np.testing.assert_allclose(np.asarray(y1[0]), want_y[t], atol=TOL)
    np.testing.assert_allclose(np.asarray(s[0]), want_s[-1], atol=TOL)
    assert np.all(np.asarray(s[1]) == 3.0)


# ------------------------------------ the decode update as a kernel (PR 55)
#
# `ops.pallas_ssm.ssd_update` interpreted, held to the plain `ssd_step` at a
# shape the kernel can tile: 16 heads of 16 in 2 groups, a state 128 wide,
# 6 lanes and the null lane's row behind them.

HK, PK, NK, GK, BK = 16, 16, 128, 2, 6


def update_inputs(seed: int):
    rng = np.random.default_rng(seed)
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    dt = jnp.asarray(np.log1p(np.exp(rng.standard_normal((BK, HK)) - 2)), jnp.float32)
    return normal(BK, HK, PK), dt, normal(BK, GK, NK), normal(BK, GK, NK)


def plain_step(s, x, dt, a, b, c, live):
    """`ssd_step` over the slot array's every row, the null lane's behind the
    batch's: what the kernel is held to."""
    rows = lambda v: jnp.pad(v, ((0, 1),) + ((0, 0),) * (v.ndim - 1))
    new, y = ssm.ssd_step(s, rows(x), rows(dt), a, rows(b), rows(c), rows(live))
    return new, y[:BK]


def bits(a):
    return np.asarray(a).view(np.uint32)


@pytest.mark.parametrize("live", [
    [False] * 6, [True] * 6, [False, True, False, False, True, True],
    [True, False, False, False, False, False],
], ids=["none", "all", "scattered", "first"])
def test_update_kernel_visits_the_live_rows_alone(live):
    from dynamo_tpu.ops import pallas_ssm
    from dynamo_tpu.ops.basics import forms_traced

    rng = np.random.default_rng(7)
    s = jnp.asarray(rng.standard_normal((BK + 1, HK, PK, NK)), jnp.float32)
    a = -jnp.exp(jnp.asarray(rng.standard_normal(HK), jnp.float32))
    x, dt, b, c = update_inputs(8)
    live = jnp.asarray(live)
    with forms_traced() as forms:
        new, y = pallas_ssm.ssd_update(s, x, dt, a, b, c, live, impl="pallas_interpret")
    assert dict(forms) == {"ssd_step_kernel": 1}
    want_s, want_y = plain_step(s, x, dt, a, b, c, live)
    visited = np.asarray(live)
    np.testing.assert_allclose(np.asarray(y)[visited], np.asarray(want_y)[visited], rtol=TOL, atol=TOL)
    np.testing.assert_allclose(np.asarray(new)[:BK][visited], np.asarray(want_s)[:BK][visited], rtol=TOL, atol=TOL)
    # a row that is not visited, the null lane's among them, is bit for bit
    # what it was, and its y is zero
    kept = ~np.pad(visited, (0, 1))
    assert np.array_equal(bits(new)[kept], bits(s)[kept])
    assert np.all(np.asarray(y)[~visited] == 0.0)


def test_update_kernel_writes_once_a_dispatch():
    """Four steps, the last alone settling, lane 1 frozen behind the second:
    every step's y and the written state are four plain steps', the three
    calls that do not settle hand on the first state untouched, and a lane
    that was never live keeps its row."""
    from dynamo_tpu.ops import pallas_ssm

    rng = np.random.default_rng(9)
    s0 = jnp.asarray(rng.standard_normal((BK + 1, HK, PK, NK)), jnp.float32)
    a = -jnp.exp(jnp.asarray(rng.standard_normal(HK), jnp.float32))
    first = np.array([True, True, False, True, False, True])
    kept, plain = s0, s0
    for h in range(4):
        live = jnp.asarray(first & ~((np.arange(BK) == 1) & (h >= 2)))
        x, dt, b, c = update_inputs(10 + h)
        kept, y = pallas_ssm.ssd_update(
            kept, x, dt, a, b, c, live, settle=h == 3, impl="pallas_interpret"
        )
        plain, want_y = plain_step(plain, x, dt, a, b, c, live)
        np.testing.assert_allclose(
            np.asarray(y)[np.asarray(live)], np.asarray(want_y)[np.asarray(live)],
            rtol=TOL, atol=TOL,
        )
        assert np.all(np.asarray(y)[~np.asarray(live)] == 0.0)
        if h < 3:
            assert isinstance(kept, pallas_ssm.Deferred) and kept.s0 is s0
            assert len(kept.steps) == h + 1 and int(kept.count[0]) == 4
    np.testing.assert_allclose(np.asarray(kept), np.asarray(plain), rtol=TOL, atol=TOL)
    dead = ~np.pad(first, (0, 1))
    assert np.array_equal(bits(kept)[dead], bits(s0)[dead])


@pytest.mark.parametrize("shape", [
    (8, 16, 16, 2),   # a state 16 wide: no tile of 128 lanes
    (8, 16, 128, 2),  # a row of 128 `dt x` holds 8 heads, two groups'
], ids=["narrow_state", "groups_share_a_row"])
def test_update_of_a_shape_the_kernel_cannot_tile_is_the_plain_form(shape):
    from dynamo_tpu.ops import pallas_ssm
    from dynamo_tpu.ops.basics import forms_traced

    H, P, N, G = shape
    assert pallas_ssm.tiling(H, P, N, G, "pallas_interpret") is None
    assert pallas_ssm.tiling(HK, PK, NK, GK, "pallas_interpret") == HK
    assert pallas_ssm.tiling(HK, PK, NK, GK, "xla") is None
    rng = np.random.default_rng(11)
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    s, x, dt = normal(3, H, P, N), normal(2, H, P), jnp.abs(normal(2, H))
    a, b, c = -jnp.abs(normal(H)), normal(2, G, N), normal(2, G, N)
    live = jnp.asarray([True, False])
    with forms_traced() as forms:
        new, y = pallas_ssm.ssd_update(
            s, x, dt, a, b, c, live, settle=False, impl="pallas_interpret"
        )
    assert dict(forms) == {"ssd_step_xla": 1}
    rows = lambda v: jnp.pad(v, ((0, 1),) + ((0, 0),) * (v.ndim - 1))
    want_s, want_y = ssm.ssd_step(s, rows(x), rows(dt), a, rows(b), rows(c), rows(live))
    assert np.array_equal(bits(new), bits(want_s)) and np.array_equal(bits(y), bits(want_y[:2]))
