"""Per-rank worker for the multi-host bring-up tests (test_multihost.py).

Usage: python multihost_worker.py <rank> <num_nodes> <model_dir> [tp] [dp] [mode]
Env: DYN_FABRIC_ADDR must point at a running fabric server.

Modes:
  serve (default): rank 0 builds the engine (leader), serves two greedy
    requests over a tp x dp mesh spanning every process, prints the
    generated tokens as one JSON line, and stops the followers. Other
    ranks replay the leader's device calls until told to stop.
  leader-hang: rank 0 rendezvouses then SLEEPS forever (short lease with
    keepalive). The test SIGKILLs it; followers must detect the expired
    leader lease and exit with rc=3 printing LEADER LOST — not hang.
"""

import asyncio
import json
import os
import sys

import jax  # the launching test sets JAX_PLATFORMS=cpu

RANK = int(sys.argv[1])
NODES = int(sys.argv[2])
MODEL_DIR = sys.argv[3]
TP = int(sys.argv[4]) if len(sys.argv) > 4 else NODES
DP = int(sys.argv[5]) if len(sys.argv) > 5 else 1
MODE = sys.argv[6] if len(sys.argv) > 6 else "serve"


async def main() -> None:
    from dynamo_tpu.engine.jax_engine.factory import build_jax_engine
    from dynamo_tpu.fabric.client import FabricClient
    from dynamo_tpu.parallel.multihost import LeaderLostError, MultiNodeConfig

    fabric = await FabricClient.connect(os.environ["DYN_FABRIC_ADDR"])
    ttl = float(os.environ.get("DYN_TEST_LEASE_TTL", "60"))
    lease = await fabric.lease_grant(ttl)

    # CONTRACT: the bring-up lease anchors the barrier data key that
    # followers use as the leader-liveness signal — it must stay alive for
    # the engine's whole lifetime, on every rank (a follower's expired
    # barrier check-in is equally fatal to re-rendezvous).
    async def keepalive() -> None:
        while True:
            await asyncio.sleep(max(0.5, ttl / 3))
            await fabric.lease_keepalive(lease)

    keepalive_task = asyncio.get_running_loop().create_task(keepalive())
    cfg = MultiNodeConfig(num_nodes=NODES, node_rank=RANK)
    engine_or_handle, _mdc = await build_jax_engine(
        MODEL_DIR,
        name="tiny",
        kv_block_size=4,
        max_batch=4,
        num_blocks=64,
        tensor_parallel_size=TP,
        data_parallel_size=DP,
        multinode=cfg,
        fabric=fabric,
        lease_id=lease,
    )
    if RANK != 0:
        handle = engine_or_handle
        handle.idle_grace_s = float(os.environ.get("DYN_TEST_IDLE_GRACE", "10"))
        try:
            await handle.serve_async()
        except LeaderLostError as e:
            print(f"LEADER LOST: {e}", flush=True)
            await fabric.close()
            os._exit(3)
        print("FOLLOWER DONE", flush=True)
        await fabric.close()
        return

    if MODE == "leader-hang":
        print("LEADER HANGING", flush=True)
        await asyncio.sleep(600)  # the test kills us long before this
        return

    from dynamo_tpu.pipeline.context import Context
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    engine = engine_or_handle

    async def one(prompt, n):
        req = PreprocessedRequest(
            token_ids=prompt,
            sampling=SamplingOptions(greedy=True),
            stop=StopConditions(max_tokens=n, ignore_eos=True),
        )
        toks = []
        async for out in engine.generate(req, Context()):
            toks.extend(out.token_ids)
        return toks

    t1 = await one(list(range(2, 14)), 5)
    t2 = await one(list(range(3, 9)), 4)
    await engine.close()
    engine.runner.stop_followers()
    print("TOKENS " + json.dumps([t1, t2]), flush=True)
    keepalive_task.cancel()
    await fabric.close()


asyncio.run(main())
