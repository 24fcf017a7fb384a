"""Decode-step latency breakdown on the live device.

Separates the three costs that add up to serving throughput:
  1. pure device compute (device-resident inputs, block_until_ready)
  2. full ModelRunner.decode serving call (host inputs + fetch)
  3. host->device transfer RTT alone

The delta between (1) and (2) is the host's share (PCIe/DMA plus Python).
Prints one JSON line per measurement.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np


def bench_it(fn, warmup=3, iters=20):
    """fn(i) is called with a fresh iteration index, so callers can vary
    the input content between timed calls."""
    for i in range(warmup):
        fn(i)
    t0 = time.perf_counter()
    for i in range(iters):
        fn(warmup + i)
    return (time.perf_counter() - t0) / iters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--prefill", type=int, default=512)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from pathlib import Path

    root = str(Path(__file__).resolve().parents[1])
    sys.path.insert(0, root)
    import __graft_entry__ as graft
    from dynamo_tpu.runtime.config import setup_jax_compilation_cache

    setup_jax_compilation_cache()
    from dynamo_tpu.engine.jax_engine.model_runner import ModelRunner

    dev = jax.devices()[0]
    print(f"device: {dev}", file=sys.stderr)

    cfg, params = graft._flagship_setup(tiny=args.tiny)
    B = args.batch
    runner = ModelRunner(
        cfg, params,
        num_blocks=max(256, B * 64), block_size=16, max_batch=B,
        max_model_len=4096, rng_seed=0,
    )

    results = {}

    # ---- 3. raw host->device RTT for the per-step input set
    tokens = np.zeros((B,), np.int32)
    positions = np.full((B,), 100, np.int32)
    bt = np.tile(np.arange(runner.max_blocks_per_seq, dtype=np.int32), (B, 1))
    slots = np.arange(B, dtype=np.int32) * 16 + 5
    temps = np.ones((B,), np.float32)
    top_ps = np.ones((B,), np.float32)
    top_ks = np.zeros((B,), np.int32)
    keys = runner._next_decode_keys(B)

    def put_all(i):
        arrs = [
            jax.device_put(a + (i % 7))
            for a in (tokens, positions, slots, temps, top_ps, top_ks)
        ] + [jax.device_put(bt + (i % 7)), jax.device_put(keys + np.uint32(i))]
        for a in arrs:
            a.block_until_ready()

    results["h2d_8arrays_ms"] = bench_it(put_all) * 1e3

    def put_one(i):
        jax.device_put(np.full((4,), i, np.int32)).block_until_ready()

    results["h2d_1array_ms"] = bench_it(put_one) * 1e3

    bump = jax.jit(lambda x, c: x + c)
    scalar_dev = jax.device_put(np.zeros((4,), np.int32))

    def fetch_one(i):
        # a fresh RESULT each time: fetching a cached array is free
        np.asarray(bump(scalar_dev, i))

    results["d2h_1array_ms"] = bench_it(fetch_one) * 1e3

    # ---- 2. serving-path decode (host numpy in, fetch out)
    def serving_step(i):
        out = runner.decode(
            tokens + (i % 16), positions, bt, slots, temps, top_ps, top_ks
        )
        return tuple(np.asarray(o) for o in out)

    serving_s = bench_it(serving_step, warmup=4, iters=15)
    results["decode_serving_ms"] = serving_s * 1e3

    # ---- 1. pure compute: device-resident inputs, reuse jitted fn
    # the step's inputs committed once, as `_launch` commits them: one
    # packed buffer, and the tokens beside it (on the device already) so
    # that each iteration can feed the last one's sample
    layout, inputs = runner._commit(
        jax.device_put(tokens), positions, bt, slots, keys,
        temps, top_ps, top_ks, np.ones(len(tokens), bool),
    )
    dev_args = [runner.params, runner.k_cache, runner.v_cache, *inputs]

    def compute_step(i):
        out, k2, v2 = runner._decode_fn(layout, *dev_args)
        # donation invalidates the cache refs; rebind for the next call,
        # and chain the sampled tokens so inputs differ every iteration
        dev_args[1], dev_args[2] = k2, v2
        dev_args[4] = out[0]
        out[0].block_until_ready()

    compute_s = bench_it(compute_step, warmup=4, iters=15)
    results["decode_compute_ms"] = compute_s * 1e3
    # donation consumed the runner's cache refs; hand back the live ones
    runner.k_cache, runner.v_cache = dev_args[1], dev_args[2]

    # ---- prefill
    ptoks = np.random.randint(0, 1000, (args.prefill,), dtype=np.int32)

    def prefill_step(i):
        r = runner.prefill(
            [int((t + i) % 1000) for t in ptoks],
            block_ids=list(range(args.prefill // 16)),
            temperature=0.0, top_p=1.0, top_k=0,
        )
        np.asarray(r[0])
        return r

    results["prefill_serving_ms"] = bench_it(prefill_step, warmup=2, iters=5) * 1e3

    results["batch"] = B
    results["tok_s_at_B_compute"] = B / compute_s
    results["tok_s_at_B_serving"] = B / serving_s
    results["device"] = str(dev)
    print(json.dumps({k: (round(v, 3) if isinstance(v, float) else v)
                      for k, v in results.items()}))


if __name__ == "__main__":
    main()
