"""The grouped product of a dropless expert layer as a Pallas TPU kernel.

`grouped_product(lhs [R, K], rhs [E, K, N], group_sizes [E]) -> [R, N]` has
the meaning of `lax.ragged_dot`: the rows are sorted by group, group `e` is
the next `group_sizes[e]` of them and multiplies `rhs[e]`; rows behind the
last group are left uncomputed (the caller zeroes them, as it does behind
XLA's). A decode step's groups are a handful of rows each, so the product
is a walk over the touched experts' weights, and XLA's kernel behind
`lax.ragged_dot` walks them at 34 to 48% of the chip's bandwidth
(`PERF.md` section 6, PRs 48 and 49).

The kernel (the form of `jax.experimental.pallas.ops.tpu.megablox`'s `gmm`,
which reads the same on the bench-top to a few per cent: `PERF.md` section
6, PR 49): a grid cell multiplies one tile of 128 rows by one group's block
of weights and keeps the rows that are the group's; the cells walk the
groups in order through scalar-prefetched metadata (`_walk`: which group
and which row tile a cell works on), so a group with no row is no cell and
costs no weight read, a row tile that several groups share stays in fast
memory between their cells, and Pallas's pipeline fetches the next cell's
weight block while this one multiplies. The tiles come from the shapes the
call sees and nothing else (`_tiling`): rows in tiles of 128; a group's
WHOLE `k` in one tile, so that a row's sum is one `dot` in float32 as XLA's
is and the result is XLA's to the bit; as much of `n` a block as 4 MiB of
weights hold (the pipeline keeps two blocks in fast memory). The stack
`rhs` goes to the kernel as it lies: unsliced, not transposed, not copied
(the benchmark's product metrics find the call by that operand).

Which form runs is the attention kernels' choice (`ops.attention
.get_attention_impl`: Pallas on the chip, XLA elsewhere,
`pallas_interpret` in the CPU tests) and the shape's: a `k` or `n` that is
no multiple of 128 lanes, rows that are no whole tiles, a `k` whose
narrowest block passes 4 MiB, or operands of two dtypes fall to
`lax.ragged_dot`, by the input alone. Each call notes the form it took
(`ops.basics.note_form`: `grouped_product_kernel`, `grouped_product_xla`),
which the goodput ledger reports for each program label's first dispatch.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.attention import get_attention_impl
from dynamo_tpu.ops.basics import note_form, run_kernel

ROW_TILE = 128  # rows a grid cell multiplies: a decode step's groups hold fewer
LANES = 128
BLOCK_BYTES = 4 << 20  # of one group's weights a grid cell; two are in flight


def _tiling(R: int, K: int, N: int, itemsize: int) -> Optional[tuple[int, int, int]]:
    """(rows, k, n) of a grid cell's tiles for a product of these sizes, or
    None where the kernel cannot tile them: whole row tiles, a group's whole
    `k`, and the widest whole number of 128-lane columns that divides `n`
    and keeps the weight block within `BLOCK_BYTES`."""
    if R % ROW_TILE or K % LANES or N % LANES:
        return None
    columns = N // LANES
    widest = max(
        (
            d for d in range(1, columns + 1)
            if columns % d == 0 and K * d * LANES * itemsize <= BLOCK_BYTES
        ),
        default=0,
    )
    return (ROW_TILE, K, widest * LANES) if widest else None


def _walk(group_sizes: jax.Array, R: int, tm: int):
    """The grid cells of one product, in the order they run: a group with
    rows takes one cell for each row tile it has a row in, the groups in
    order, so the cells of one row tile follow one another. Returns (the
    group of each cell, its row tile, the groups' first rows `[E + 1]`, how
    many cells there are); the first two are `R // tm + E - 1` long, the most
    there can be, and hold the last group and tile behind the last cell.
    Sums and compares over `[cells, E]`: no scatter, no device loop."""
    E = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    first_tile = (ends - group_sizes) // tm
    tiles = jnp.where(group_sizes > 0, (ends + tm - 1) // tm - first_tile, 0)
    cell_ends = jnp.cumsum(tiles)
    cell = jnp.arange(R // tm + E - 1, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.sum(cell[:, None] >= cell_ends[None, :], axis=1, dtype=jnp.int32),
        E - 1,
    )
    its = group[:, None] == jnp.arange(E, dtype=jnp.int32)[None, :]
    of_group = lambda x: jnp.sum(jnp.where(its, x[None, :], 0), axis=1, dtype=jnp.int32)
    tile = of_group(first_tile) + cell - of_group(cell_ends - tiles)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends.astype(jnp.int32)])
    return group, jnp.clip(tile, 0, R // tm - 1), offsets, cell_ends[-1]


def _pallas_product(lhs, rhs, group_sizes, tiling, interpret: bool):
    tm, K, tn = tiling
    R, N = lhs.shape[0], rhs.shape[2]
    group, tile, offsets, cells = _walk(group_sizes, R, tm)

    def kernel(group, tile, offsets, lhs_ref, rhs_ref, out_ref):
        cell = pl.program_id(1)
        g = group[cell]
        row = tile[cell] * tm + lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
        product = jnp.dot(
            lhs_ref[...], rhs_ref[...], preferred_element_type=jnp.float32
        )
        # the tile's other rows are another group's, written by its cell
        # before or after this one while the tile stays in fast memory
        out_ref[...] = jnp.where(
            (row >= offsets[g]) & (row < offsets[g + 1]),
            product, out_ref[...].astype(jnp.float32),
        ).astype(out_ref.dtype)

    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((R, N), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, K), lambda n, c, group, tile, offsets: (tile[c], 0)),
                pl.BlockSpec(
                    (None, K, tn), lambda n, c, group, tile, offsets: (group[c], 0, n)
                ),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda n, c, group, tile, offsets: (tile[c], n)
            ),
            grid=(N // tn, cells),  # a block of columns at a time, down the walk
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )
    return run_kernel(call, group, tile, offsets, lhs, rhs)


def grouped_product(
    lhs: jax.Array,  # [R, K] rows sorted by group
    rhs: jax.Array,  # [E, K, N] one matrix a group
    group_sizes: jax.Array,  # [E] int32; rows behind their sum are not computed
    impl: Optional[str] = None,
) -> jax.Array:
    """`lax.ragged_dot(lhs, rhs, group_sizes)`, in the Pallas kernel where
    `impl` asks for it and the shapes can be tiled (the module's docstring)."""
    impl = get_attention_impl(impl)
    tiling = (
        _tiling(lhs.shape[0], *rhs.shape[1:], rhs.dtype.itemsize)
        if impl.startswith("pallas") and lhs.dtype == rhs.dtype else None
    )
    if tiling is None:
        note_form("grouped_product_xla")
        return lax.ragged_dot(lhs, rhs, group_sizes)
    note_form("grouped_product_kernel")
    return _pallas_product(
        lhs, rhs, group_sizes, tiling, interpret=impl == "pallas_interpret"
    )
