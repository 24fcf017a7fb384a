"""Bench-top timing of the Mamba-2 decode update alone.

A decode step of the Mamba-2 family updates every live lane's state in each
of its Mamba-2 layers (`ops.pallas_ssm.ssd_update`). This times one dispatch
of four steps over five layers' slot arrays at the published sizes (a lane's
state `[128, 64, 128]` float32, 4 MiB; 65 rows a layer), each step's input
hanging on the step before it as a model's does, for a few counts of live
lanes scattered among 64, in the plain XLA form and in the Pallas kernel, and
holds the two to each other. It needs the chip: times from anywhere else mean
nothing, so it refuses to run without one.

    chiprun -- python benchmarks/ssm_step_benchtop.py
    ... --lanes 19 48 --block-mib 1        # another block of the state
    ... --lanes 19 --ops                   # and the kernel form's device operations
    JAX_PLATFORMS=cpu python ... --rehearse   # its control flow, interpreted, toy sizes

Prints one JSON line a reading: milliseconds a step (five layers) that the
device is busy in traced dispatches (the host's clock around a dispatch of
twenty small calls reads a third of a millisecond a step more: PERF.md
section 6, PR 55), the bytes a step needs (each live lane's state read once a step and
written once a dispatch: `cellbench/counts/ssm2_moe_decode.py`
`scan_state_step_bytes`), and those bytes over the device's time.
"""

from __future__ import annotations

import argparse
import functools
import json
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.ops import pallas_ssm

# heads, head width, state width, groups, Mamba-2 layers
SHAPE = (128, 64, 128, 8, 5)
TOY = (16, 16, 128, 2, 2)  # `--rehearse`
B, HORIZON = 64, 4
BANDWIDTH = 819e9


def _dispatch(impl, a, states, inputs, lives):
    """`HORIZON` steps over the layers, the last one settling. inputs: (x,
    dt, b, c), each `[HORIZON, layers, ...]`; lives [HORIZON, B]. Returns
    (the states, every y)."""
    ys, y = [], 0.0
    states = list(states)
    for h in range(HORIZON):
        for l in range(len(states)):
            x, dt, b, c = (v[h, l] for v in inputs)
            # a layer's input hangs on what ran before it, as a model's does
            states[l], y = pallas_ssm.ssd_update(
                states[l], x + 1e-30 * y, dt, a, b, c, lives[h],
                settle=h == HORIZON - 1, impl=impl,
            )
            ys.append(y)
    return tuple(states), jnp.stack(ys)


def _traced(dispatch, a, states, inputs, lives, dispatches: int):
    """`dispatches` traced runs: (milliseconds a step that the device is busy,
    its operations by their time, the largest first: [label, milliseconds a
    step])."""
    from cellbench import trace_reduce as tr

    with tempfile.TemporaryDirectory() as where:
        with jax.profiler.trace(where):
            for _ in range(dispatches):
                states, _ = jax.block_until_ready(dispatch(a, states, inputs, lives))
        reduced = tr.reduce_device(tr.load_xplane(tr.find_xplane(where)))
    a_step = lambda seconds: round(1e3 * seconds / dispatches / HORIZON, 4)
    ops = [[name, a_step(seconds)] for name, seconds in tr.top_ops(reduced, 12)]
    return a_step(reduced["busy_s"]), ops


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--lanes", nargs="*", type=int, default=[8, 19, 34, 48, 64])
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--block-mib", type=float)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ops", action="store_true")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        raise SystemExit(f"needs a TPU; found {device.platform}")
    H, P, N, G, layers = TOY if args.rehearse else SHAPE
    lanes_total = 6 if args.rehearse else B
    kernel_impl = "pallas_interpret" if args.rehearse else "pallas"
    if args.block_mib:
        pallas_ssm.BLOCK_BYTES = int(args.block_mib * 2**20)
    key = jax.random.PRNGKey(args.seed)
    draw = lambda i, shape: jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
    a = -jnp.exp(draw(0, (H,)))
    every = (HORIZON, layers, lanes_total)
    inputs = (
        draw(1, every + (H, P)), jax.nn.softplus(draw(2, every + (H,)) - 2.0),
        draw(3, every + (G, N)), draw(4, every + (G, N)),
    )
    fresh = jax.jit(lambda: tuple(
        draw(1000 + l, (lanes_total + 1, H, P, N)) for l in range(layers)
    ))
    forms = {
        f: jax.jit(functools.partial(_dispatch, impl), donate_argnums=(1,))
        for f, impl in (("xla", "xla"), ("kernel", kernel_impl))
    }
    rng = np.random.default_rng(args.seed)
    state_bytes = H * P * N * 4
    for lanes in ([0, 3] if args.rehearse else args.lanes):
        live = np.zeros((HORIZON, lanes_total), bool)
        live[:, rng.permutation(lanes_total)[:lanes]] = True
        if lanes:  # one lane freezes behind the second step
            live[2:, np.flatnonzero(live[0])[0]] = False
        lives = jnp.asarray(live)
        needed = (1 + 1 / HORIZON) * lanes * layers * state_bytes
        out, busy = {}, {}
        for f, dispatch in forms.items():
            states, ys = dispatch(a, fresh(), inputs, lives)
            out[f] = (np.asarray(states[0]), np.asarray(states[-1]), np.asarray(ys))
            if not args.rehearse:  # the CPU's profile has no device to read
                busy[f], ops = _traced(dispatch, a, states, inputs, lives, args.repeats)
                if args.ops and f == "kernel":
                    print(json.dumps({"lanes": lanes, "device_ops_ms_a_step": ops}), flush=True)
            del states, ys
        first = np.asarray(fresh()[0])
        dead = ~np.pad(live[0], (0, 1))
        close = lambda x, y: bool(np.allclose(x, y, rtol=1e-5, atol=1e-5))
        # every step's y of the lanes live in it (the plain form computes the
        # others' too, the kernel leaves them zero)
        lived = np.repeat(live, layers, axis=0)
        print(json.dumps({
            "device": device.device_kind, "lanes": lanes, "of": lanes_total,
            "layers": layers, "horizon": HORIZON,
            "block_heads": pallas_ssm.tiling(H, P, N, G, kernel_impl),
            "needed_bytes_a_step": needed,
            **{f"{f}_device_ms_a_step": t for f, t in busy.items()},
            **{f"{f}_gb_s": round(needed / t / 1e6, 1) for f, t in busy.items()},
            **{
                f"{f}_share_of_bandwidth": round(needed / BANDWIDTH / t * 1e3, 4)
                for f, t in busy.items()
            },
            "y_close": close(out["kernel"][2][lived], out["xla"][2][lived]),
            "state_close": all(close(out["kernel"][i], out["xla"][i]) for i in (0, 1)),
            "dead_rows_bit_for_bit": bool(
                np.array_equal(out["kernel"][0][dead].view(np.uint32), first[dead].view(np.uint32))
            ),
        }), flush=True)


if __name__ == "__main__":
    main()
