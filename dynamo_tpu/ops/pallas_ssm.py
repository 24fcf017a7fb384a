"""The Mamba-2 decode update (`ops.ssm.ssd_step`) as a Pallas TPU kernel that
visits the live lanes' state only.

A lane's state is 4 MiB at the published sizes (`[128 heads, 64, 128]`
float32) and a decode step updates it in five layers; XLA's dense form moves
every row of the slot array under a mask, the idle lanes' and the null
lane's with them. Here a grid cell is one live lane (by heads, `tiling`):
the rows come from the live mask through scalar prefetch (`live_rows`), the
state operand is the slot array as it lies, `[S, H, P, N]`, and its block's
index is read from the list, so a row nobody decodes in costs no copy and,
the array aliased in and out, is bit for bit what it was.

Read once a step, written once a dispatch. A dispatch of `H` unrolled steps
(`ModelRunner._decode_multi_impl`) says which step is its last (`settle`).
A step that does not settle reads the dispatch's first state for the rows
live at its start, applies the dispatch's steps so far in registers and
emits `y`: it has no state output, and what the layer keeps until its next
step is a `Deferred` (the first state untouched, the rows, and each step's
decay, `dt x` and `B`). The step that settles does the same and writes the
row. So a live lane's state crosses the bus `1 + 1/H` times a step, which is
what XLA's fusions did over all rows. A lane that freezes inside a dispatch
is still visited, and its later steps are the identity (`dt` 0: decay 1,
input 0), so what it had is what is written. Float32 throughout, a value's
operations in `ssd_step`'s order.

In a cell. The state's tiles hold eight of a head's `P` rows down the
sublanes and `N` along the lanes, so `dt x`, one value a row, has to be
broadcast along the lanes, which the vector unit cannot do: a step's `dt x`
comes as it lies, rows of 128 lanes (`_packed`, the decay a head in eight
more rows under them), and a row becomes 128 columns by a broadcast down the
sublanes and the transpose unit (`_columns`: 64 transposes of `[128, 128]` a
lane and step applied, the kernel's bound once a call applies three steps).
The product with `C` is a float32 product on the vector unit and its sum
along the lanes on the matrix unit, which is idle otherwise (`_row_sums`), and
`y` leaves as rows of 128 lanes too. What the bench-top read of each choice
is in `PERF.md` section 6, PR 55.

Which form runs is the attention kernels' choice (`get_attention_impl`:
Pallas on the chip, `pallas_interpret` in the CPU tests, XLA elsewhere) and
the shape's (`tiling`): the plain `ssd_step` where `N` is not the 128 lanes,
a row of 128 `dt x` is not whole heads of one group, or the heads pass one
tile of lanes. Each call notes its form (`ssd_step_kernel`, `ssd_step_xla`).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops import ssm
from dynamo_tpu.ops.attention import get_attention_impl
from dynamo_tpu.ops.basics import note_form, run_kernel

F32 = jnp.float32
LANES, SUBLANES = 128, 8
# of a lane's state a grid cell: the pipeline keeps two blocks in flight in
# and, where the call settles, two out, beside the small operands
BLOCK_BYTES = 4 << 20
VMEM_LIMIT = 48 << 20


class Deferred(NamedTuple):
    """What a Mamba-2 layer keeps between two steps of one dispatch where the
    kernel runs: the slot array as the dispatch found it, the rows live at
    its first step (`live_rows`), and the steps applied so far, each
    `(_packed [B, H P / 128 + 8, 128], B [B, G, N])`."""

    s0: jax.Array
    rows: jax.Array
    count: jax.Array
    steps: tuple


def tiling(H: int, P: int, N: int, G: int, impl: Optional[str] = None) -> Optional[int]:
    """Heads of a grid cell's block of the state where the kernel runs, None
    where the plain form does: `impl` asks for XLA (`get_attention_impl`), or
    the kernel cannot tile the shape. Whole groups of heads, as many as
    `BLOCK_BYTES` hold and divide the lane's."""
    if not get_attention_impl(impl).startswith("pallas"):
        return None
    if N != LANES or P % SUBLANES or LANES % P or H > LANES or H % G:
        return None
    if (H // G) % (LANES // P):  # a row of 128 `dt x` holds one group's heads
        return None
    fit = max(
        (d for d in range(1, G + 1) if G % d == 0 and d * (H // G) * P * N * 4 <= BLOCK_BYTES),
        default=0,
    )
    return fit * (H // G) or None


def live_rows(live: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(the rows of the live lanes in order, `[B]` int32, row 0 behind them;
    how many there are, `[1]` int32: the grid's bound, so that no cell runs
    behind them)."""
    (rows,) = jnp.nonzero(live, size=live.shape[0], fill_value=0)
    return rows.astype(jnp.int32), jnp.sum(live, dtype=jnp.int32).reshape(1)


def _packed(x, dt, a, live):
    """One step's per-head operands as rows of 128 lanes: `dt x` as it lies,
    `[B, H P / 128, 128]` (a row holds `128 / P` heads), and under it eight
    rows of the decay `exp(dt a)`, a head a lane. A lane that is not live in
    this step gets the identity (`dt` 0)."""
    dt = jnp.where(live[:, None], dt, 0.0)
    B, H = dt.shape
    decay = jnp.pad(jnp.exp(dt * a), ((0, 0), (0, LANES - H)))
    dtx = (dt[..., None] * x).reshape(B, -1, LANES)
    return jnp.concatenate(
        [dtx, jnp.broadcast_to(decay[:, None, :], (B, SUBLANES, LANES))], axis=1
    )


def _columns(row):
    """`row [1, 128]` as columns: `[128, 128]`, value `i` along the lanes of
    row `i` (a broadcast down the sublanes, then the transpose unit: the
    vector unit has no broadcast along lanes of its own)."""
    return jnp.broadcast_to(row, (LANES, LANES)).T


def _row_sums(t):
    """`t [128, 128]` float32 summed along its lanes, as one row `[1, 128]`, on
    the matrix unit: `t` is the sum of three bfloat16 parts to the last bit
    (8 of its 24 significant bits each), each part's product with ones is
    exact, and the unit adds them in float32, so the sums are float32 sums of
    `t`'s own values. (The transpose unit's way, `sum(t.T, axis=0)`, costs the
    kernel a third of its time there: `PERF.md` section 6, PR 55.)"""
    hi = t.astype(jnp.bfloat16)
    rest = t - hi.astype(F32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(F32)).astype(jnp.bfloat16)
    ones = jnp.ones((2 * SUBLANES, t.shape[1]), jnp.bfloat16)
    summed = lambda part: lax.dot_general(
        ones, part, (((1,), (1,)), ((), ())), preferred_element_type=F32
    )
    return ((summed(lo) + summed(mid)) + summed(hi))[:1]


def _kernel(steps: int, settle: bool, shape: tuple, hb: int):
    H, P, N, G = shape
    R = H // G  # heads of a group
    groups = hb // R  # groups of a cell
    hp = LANES // P  # heads of a row of 128 lanes
    rows_of_group = R // hp
    dtx_rows = H * P // LANES

    def kernel(rows, count, s_ref, *refs):
        packed = refs[0: 2 * steps: 2]
        bs = refs[1: 2 * steps: 2]
        c_ref = refs[2 * steps]
        y_ref = refs[2 * steps + 1]
        o_ref = refs[2 * steps + 2] if settle else None
        decays = refs[-1]  # scratch [steps, 128 heads, 128]: a head's decay a row
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _():
            for k, p_ref in enumerate(packed):
                decays[k] = _columns(p_ref[dtx_rows: dtx_rows + 1, :])

        def group(g, carry):
            gi = j * groups + g  # the group among the lane's
            b_rows = [b_ref[pl.ds(gi, 1), :] for b_ref in bs]
            c_row = c_ref[pl.ds(gi, 1), :]
            for i in range(rows_of_group):
                row = gi * rows_of_group + i  # of the lane's rows of `dt x`
                h = (g * rows_of_group + i) * hp  # its first head in the block
                head = j * hb + h  # and among the lane's
                s = s_ref[pl.ds(h, hp)].reshape(hp * P, N)
                for k in range(steps):
                    decay = jnp.concatenate([
                        jnp.broadcast_to(
                            decays[k, pl.ds(head + q, 1), :], (P, LANES)
                        ) for q in range(hp)
                    ], axis=0)
                    dtx = _columns(packed[k][pl.ds(row, 1), :])
                    s = decay * s + dtx * b_rows[k]
                y_ref[pl.ds(row, 1), :] = _row_sums(s * c_row)
                if settle:
                    o_ref[pl.ds(h, hp)] = s.reshape(hp, P, N)
            return carry

        lax.fori_loop(0, groups, group, 0)

    return kernel


def _pallas_update(state, rows, count, steps, c, settle, hb, interpret):
    S, H, P, N = state.shape
    B, G = c.shape[:2]
    K = len(steps)
    dtx_rows = H * P // LANES
    lane_tile = lambda shape: pl.BlockSpec(
        (None,) + shape, lambda i, j, rows, count: (rows[i], 0, 0)
    )
    state_spec = pl.BlockSpec(
        (None, hb, P, N), lambda i, j, rows, count: (rows[i], j, 0, 0)
    )
    small = [
        spec for _ in range(K)
        for spec in (lane_tile((dtx_rows + SUBLANES, LANES)), lane_tile((G, N)))
    ]
    y_shape = jax.ShapeDtypeStruct((B, dtx_rows, LANES), F32)
    out_shape, out_specs, aliases = [y_shape], [lane_tile((dtx_rows, LANES))], {}
    if settle:
        out_shape.append(jax.ShapeDtypeStruct(state.shape, F32))
        out_specs.append(state_spec)
        aliases = {2: 1}  # the state, behind the two prefetched scalars
    call = pl.pallas_call(
        _kernel(K, settle, (H, P, N, G), hb),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[state_spec] + small + [lane_tile((G, N))],
            out_specs=out_specs,
            grid=(count[0], H // hb),
            scratch_shapes=[pltpu.VMEM((K, LANES, LANES), F32)],
        ),
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
    )
    flat = [a for step in steps for a in step]
    out = run_kernel(call, rows, count, state, *flat, c)
    return out[0], (out[1] if settle else None)


def ssd_update(
    state: Union[jax.Array, Deferred],  # [S, H, P, N] float32, or what the
                                        # dispatch's earlier steps deferred
    x: jax.Array,   # [B, H, P] float32
    dt: jax.Array,  # [B, H] behind its softplus
    a: jax.Array,   # [H] negative
    b: jax.Array,   # [B, G, N]
    c: jax.Array,   # [B, G, N]
    live: jax.Array,  # [B] bool
    *,
    settle: bool = True,
    impl: Optional[str] = None,
):
    """One token for every lane of a decode batch; lane `i`'s state is row
    `i` of `state`, whose rows behind the lanes (the null lane's) no lane
    decodes in. Returns (what the layer keeps, y [B, H, P]): the slot array,
    or, from the kernel with `settle` false, a `Deferred` that the
    dispatch's next step takes in its place; the lanes live in a later step
    of a dispatch are among those live in its first. `y` of a lane the kernel
    does not visit is zero (the plain form computes every row's)."""
    first = state.s0 if isinstance(state, Deferred) else state
    S, H, P, N = first.shape
    B, G = b.shape[:2]
    hb = tiling(H, P, N, G, impl)
    if hb is None:
        note_form("ssd_step_xla")
        # every row under one mask, the null lane's with them: no slice of
        # the array, so XLA writes it where it lies
        rows_of = lambda v: jnp.pad(v, ((0, S - B),) + ((0, 0),) * (v.ndim - 1))
        new, y = ssm.ssd_step(
            first, rows_of(x), rows_of(dt), a, rows_of(b), rows_of(c), rows_of(live)
        )
        return new, y[:B]
    note_form("ssd_step_kernel")
    if isinstance(state, Deferred):
        rows, count, steps = state.rows, state.count, state.steps
    else:
        (rows, count), steps = live_rows(live), ()
    steps = steps + ((_packed(x, dt, a, live), b),)
    y, new = _pallas_update(
        first, rows, count, steps, c, settle, hb,
        get_attention_impl(impl) == "pallas_interpret",
    )
    y = jnp.where(live[:, None, None], y.reshape(B, H, P), 0.0)
    return (new if settle else Deferred(first, rows, count, steps)), y
