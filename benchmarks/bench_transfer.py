"""Disagg KV transfer microbench: device-native (colocated) path vs the
msgpack/TCP wire path.

Prints ONE JSON line:
    {"metric": "disagg_transfer_speedup", "value": <device/wire ratio>,
     "device_gbps": ..., "wire_gbps": ..., ...}

The wire path measured here is extract->host fetch->msgpack encode->decode
->inject (the TCP socket itself would only make it slower, so the measured
ratio is a LOWER bound on the real advantage). Ref exemplar the device path
replaces: NIXL GPUDirect RDMA (docs/architecture/disagg_serving.md:76-118).

Usage: python benchmarks/bench_transfer.py [--blocks N] [--reps R] [--big]
"""

from __future__ import annotations

import argparse
import json
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--blocks", type=int, default=64)
    parser.add_argument("--reps", type=int, default=10)
    parser.add_argument(
        "--big", action="store_true",
        help="llama3-8b-shaped caches (TPU); default tiny (CPU-friendly)",
    )
    parser.add_argument(
        "--medium", action="store_true",
        help="MB-scale KV payloads on CPU (realistic cache geometry)",
    )
    parser.add_argument(
        "--tpu", action="store_true",
        help="run on the TPU backend (default: force CPU)",
    )
    parser.add_argument(
        "--reshard", default=None, metavar="PTP,DTP",
        help="asymmetric-TP mode, e.g. '1,2' or '2,4': source cache on a "
        "tp=PTP mesh, dest on a DISTINCT tp=DTP mesh — measures the "
        "cross-mesh reshard copy (the reference's block_copy.cu case)",
    )
    args = parser.parse_args()

    import jax

    if not args.tpu:
        jax.config.update("jax_platforms", "cpu")
    import msgpack
    import numpy as np

    from dynamo_tpu.disagg.protocols import KvBlockPayload
    from dynamo_tpu.disagg.transfer import from_wire_array, to_wire_array
    from dynamo_tpu.engine.jax_engine.model_runner import ModelRunner
    from dynamo_tpu.models import llama as L

    if args.big:
        cfg = L.LlamaConfig.llama3_8b()
        import __graft_entry__ as graft

        cfg, params = graft._flagship_setup(tiny=False)
        block_size = 16
    elif args.medium:
        # KV-realistic shapes (llama3-8b cache geometry, 8 layers) so the
        # payload is MBs — the regime where serialization cost shows
        cfg = L.LlamaConfig(
            vocab_size=256, hidden_size=256, intermediate_size=512,
            num_layers=8, num_heads=8, num_kv_heads=8, head_dim=128,
            max_position_embeddings=4096,
        )
        params = L.init_params(cfg, jax.random.PRNGKey(0))
        block_size = 16
    else:
        cfg = L.LlamaConfig.tiny(vocab_size=256)
        if args.reshard:
            # both TP degrees must divide the kv-head count: derive it
            # from the requested shape instead of capping at 4
            import dataclasses

            import math

            tps = [int(x) for x in args.reshard.split(",")]
            heads = math.lcm(4, *tps)
            # every column/row-parallel dim must divide by each TP degree:
            # derive the whole geometry from the head count
            cfg = dataclasses.replace(
                cfg, num_kv_heads=heads, num_heads=heads,
                hidden_size=heads * 16, intermediate_size=heads * 32,
                vocab_size=heads * 32,
            )
        params = L.init_params(cfg, jax.random.PRNGKey(0))
        block_size = 16

    nb = args.blocks + 8

    def mk(devices=None, tp=1):
        mesh = kv_sharding = None
        p = params
        if devices is not None:
            from dynamo_tpu.parallel.mesh import build_mesh
            from dynamo_tpu.parallel.sharding import shard_llama

            mesh = build_mesh(tp=tp, devices=devices)
            p, kv_sharding = shard_llama(mesh, cfg, params)
        return ModelRunner(
            cfg, p, num_blocks=nb, block_size=block_size,
            max_batch=4, max_model_len=args.blocks * block_size,
            mesh=mesh, kv_sharding=kv_sharding,
        )

    reshard = None
    if args.reshard:
        p_tp, d_tp = (int(x) for x in args.reshard.split(","))
        devs = jax.devices()
        need = p_tp + d_tp
        if len(devs) < need:
            raise SystemExit(
                f"--reshard {args.reshard} needs {need} devices, "
                f"have {len(devs)} (CPU: XLA_FLAGS="
                "--xla_force_host_platform_device_count=8)"
            )
        src = mk(devices=devs[:p_tp], tp=p_tp)
        dst = mk(devices=devs[p_tp : p_tp + d_tp], tp=d_tp)
        reshard = (p_tp, d_tp)
    else:
        src, dst = mk(), mk()
    ids = list(range(1, args.blocks + 1))
    block_bytes = (
        2 * cfg.num_layers * cfg.num_kv_heads * args.blocks * block_size
        * cfg.head_dim * 2
    )

    def device_round() -> None:
        k, v, _n = src.extract_blocks_device(ids)
        dst.inject_blocks_device(ids, k, v)
        jax.block_until_ready(dst.k_cache)

    def wire_round() -> None:
        kh, vh = src.extract_blocks(ids)
        wire = msgpack.packb(
            KvBlockPayload.from_arrays(
                to_wire_array(kh), to_wire_array(vh), kh.dtype.name
            ).to_wire()
        )
        payload = KvBlockPayload.from_wire(msgpack.unpackb(wire, raw=False))
        k2, v2 = payload.to_arrays()
        dst.inject_blocks(
            ids, from_wire_array(k2, payload.dtype),
            from_wire_array(v2, payload.dtype),
        )
        jax.block_until_ready(dst.k_cache)

    # warmup with the EXACT measured call pattern (the first two calls of
    # a jitted fn can compile twice — committed-device argument signatures
    # differ between a cold and a steady-state call)
    for _ in range(2):
        device_round()
        wire_round()

    t0 = time.perf_counter()
    for _ in range(args.reps):
        device_round()
    dev_s = (time.perf_counter() - t0) / args.reps

    t0 = time.perf_counter()
    for _ in range(args.reps):
        wire_round()
    wire_s = (time.perf_counter() - t0) / args.reps

    print(
        json.dumps(
            {
                "metric": "disagg_transfer_speedup",
                "value": round(wire_s / dev_s, 2),
                "unit": "x (device-path vs wire-path)",
                "vs_baseline": None,
                "device_gbps": round(block_bytes / dev_s / 1e9, 3),
                "wire_gbps": round(block_bytes / wire_s / 1e9, 3),
                "payload_mib": round(block_bytes / 2**20, 2),
                "blocks": args.blocks,
                "reshard": (
                    f"tp{reshard[0]}->tp{reshard[1]}" if reshard else None
                ),
                "device": str(jax.devices()[0].platform),
                "model": "llama3-8b" if args.big else ("medium" if args.medium else "tiny"),
            }
        )
    )


if __name__ == "__main__":
    main()
