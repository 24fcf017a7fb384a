"""Bench-top timing of a decode step's grouped products alone.

A decode step of a sparse-expert model calls `ops.moe.dropless_experts` once
an expert layer; nearly all of its device time is the grouped products over
the layer's weight stacks (`ops.grouped_product`). This times one step's
worth of them, every expert layer with its own stacks at a cell's published
sizes and each layer's result feeding the next so that they run one after
another, for a few counts of live lanes among 64, in the Pallas kernel and in
`lax.ragged_dot`, and holds the two to each other bit for bit. It needs the
chip: times from anywhere else mean nothing, so it refuses to run without one.

    chiprun -- python benchmarks/grouped_product_benchtop.py
    ... --shapes joyai-flash-l5 --lanes 12 3 --block-mib 2   # another block
    ... --gmm   # the installed megablox `gmm` at the same tiles, beside
    JAX_PLATFORMS=cpu python ... --rehearse   # its control flow, interpreted, toy sizes

Prints one JSON line a reading: milliseconds a step, the bytes of the experts
the routing touched, and those bytes over the chip's 819 GB/s over the time.
"""

from __future__ import annotations

import argparse
import functools
import importlib
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dynamo_tpu.ops import grouped_product as G
from dynamo_tpu.ops.basics import run_kernel
from dynamo_tpu.ops.moe import _grouped_ffn

# held experts, the router's width, experts a token, hidden (the latent in
# Nemotron's), an expert's width, expert layers, form
SHAPES = {
    "lfm2-8b-a1b-l16": (32, 32, 4, 2048, 1792, 14, "swiglu"),
    "joyai-flash-l5": (256, 256, 8, 2048, 768, 4, "swiglu"),
    "nemotron3-super-l11-e128": (128, 512, 22, 1024, 2688, 5, "relu2"),
}
LANES = {
    "lfm2-8b-a1b-l16": (38, 20), "joyai-flash-l5": (12, 3),
    "nemotron3-super-l11-e128": (40, 20),
}
TOYS = {  # `--rehearse`
    "toy-swiglu": (8, 8, 2, 256, 128, 2, "swiglu"),
    "toy-relu2": (8, 16, 4, 128, 256, 2, "relu2"),
}
B = 64
BANDWIDTH = 819e9


def _step(form: str, product, xs, sizes, stacks):
    """A step's expert layers one after another on the same sorted rows."""
    live = (jnp.arange(xs.shape[0]) < jnp.sum(sizes[0]))[:, None]
    for layer, group_sizes in zip(stacks, sizes):
        if form == "relu2":
            up = jax.nn.relu(product(xs, layer["wu"], group_sizes))
            ys = product(up * up, layer["wd"], group_sizes)
        else:
            ys = _grouped_ffn(
                xs, group_sizes, layer["wg"], layer["wu"], layer["wd"], product
            )
        xs = jnp.where(live, ys, 0).astype(xs.dtype)
    return xs


def _megablox(impl: str, lhs, rhs, group_sizes):
    """The installed `megablox.gmm` at the module's tiles, named as the
    program's kernels are: what PR 49 read the repo's own kernel against."""
    gmm = importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm").gmm
    return run_kernel(
        functools.partial(
            gmm.__wrapped__, preferred_element_type=lhs.dtype,
            tiling=G._tiling(lhs.shape[0], *rhs.shape[1:], rhs.dtype.itemsize),
            interpret=impl == "pallas_interpret",
        ),
        lhs, rhs, group_sizes,
    )


def _routing(rng, held, width, k, layers, lanes):
    """Each live lane's `k` distinct experts of `width`, those under `held`
    kept: group sizes `[layers, held]`, every layer the same count of rows
    (the largest is padded down to the smallest so that `live` is one mask)."""
    sizes = np.zeros((layers, held), np.int32)
    for layer in range(layers):
        for _ in range(lanes):
            chosen = rng.permutation(width)[:k]
            np.add.at(sizes[layer], chosen[chosen < held], 1)
    rows = sizes.sum(axis=1).min()
    for layer in range(layers):  # trim the busiest groups to a common total
        while sizes[layer].sum() > rows:
            sizes[layer, sizes[layer].argmax()] -= 1
    return sizes


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="*")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--gmm", action="store_true")
    ap.add_argument("--lanes", nargs="*", type=int)
    ap.add_argument("--repeats", type=int, default=20)
    ap.add_argument("--block-mib", type=float)
    ap.add_argument("--row-tile", type=int)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        raise SystemExit(f"needs a TPU; found {device.platform}")
    shapes = TOYS if args.rehearse else SHAPES
    impl = "pallas_interpret" if args.rehearse else "pallas"
    if args.block_mib:
        G.BLOCK_BYTES = int(args.block_mib * 2**20)
    if args.row_tile:
        G.ROW_TILE = args.row_tile

    for name in args.shapes or shapes:
        held, width, k, D, F, layers, form = shapes[name]
        key = jax.random.PRNGKey(args.seed)
        draw = lambda i, shape: (
            jax.random.normal(jax.random.fold_in(key, i), shape, jnp.bfloat16)
            * shape[1] ** -0.5
        )
        stacks = [
            {"wu": draw(3 * i, (held, D, F)), "wd": draw(3 * i + 1, (held, F, D))}
            | ({} if form == "relu2" else {"wg": draw(3 * i + 2, (held, D, F))})
            for i in range(layers)
        ]
        xs = jax.random.normal(jax.random.fold_in(key, 999), (B * k, D), jnp.bfloat16)
        forms = {
            "kernel": functools.partial(G.grouped_product, impl=impl),
            "xla": lax.ragged_dot,
        } | ({"gmm": functools.partial(_megablox, impl)} if args.gmm else {})
        steps = {
            f: jax.jit(functools.partial(_step, form, product))
            for f, product in forms.items()
        }
        rng = np.random.default_rng(args.seed)
        for lanes in args.lanes or LANES.get(name, (40, 3)):
            sizes = jnp.asarray(_routing(rng, held, width, k, layers, lanes))
            touched = int((np.asarray(sizes) > 0).sum())
            bytes_read = touched * len(stacks[0]) * D * F * 2
            out, ms = {}, {}
            for f, step in steps.items():
                out[f] = step(xs, sizes, stacks).block_until_ready()
                times = []
                for _ in range(args.repeats):
                    t0 = time.perf_counter()
                    step(xs, sizes, stacks).block_until_ready()
                    times.append(time.perf_counter() - t0)
                ms[f] = float(np.median(times)) * 1e3
            bits = lambda a: np.asarray(a).view(np.uint16)
            same = {f: bool(np.array_equal(bits(out[f]), bits(out["xla"]))) for f in out}
            print(json.dumps({
                "shapes": name, "device": device.device_kind, "lanes": lanes,
                "rows": B * k, "live_rows": int(np.asarray(sizes)[0].sum()),
                "experts_touched_per_layer": touched / layers,
                "tiling": {
                    s: G._tiling(B * k, *stacks[0][s].shape[1:], 2)
                    for s in stacks[0]
                },
                "bytes": bytes_read,
                **{f"{f}_ms": round(t, 4) for f, t in ms.items()},
                **{
                    f"{f}_share_of_bandwidth": round(bytes_read / BANDWIDTH / t * 1e3, 4)
                    for f, t in ms.items()
                },
                "bit_for_bit": all(same.values()), "same_as_xla": same,
            }), flush=True)
        del stacks, steps, out


if __name__ == "__main__":
    main()
