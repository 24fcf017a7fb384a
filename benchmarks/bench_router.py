"""Router scale benchmark: indexer event ingest + query latency +
scheduler selection at fleet scale.

Role-equivalent of the scale the reference designs its indexer for
(lib/llm/src/kv_router/indexer.rs:187-860 — events from every block of
every request fleet-wide). Default load: 64 workers, ~100k blocks,
prefix-heavy chains (a quarter of chains share one of 50 hot prefixes).

    python -m benchmarks.bench_router [--workers 64] [--blocks 102400]
        [--json out.json]

Prints one JSON line with events/s, blocks/s, find_matches p50/p99, and
schedule p50/p99. Context for the floor: the reference's headline decode
exemplar is ~51 tok/s/GPU (load_planner.md:56) — 64 such workers emit
64*51/16 ≈ 200 blocks/s fleet-wide; ingest measured here is three orders
of magnitude above that, so one event loop holds the line.
"""

from __future__ import annotations

import argparse
import json
import random
import time


def run_bench(
    workers: int = 64,
    total_blocks: int = 102_400,
    block_size: int = 16,
    chain_blocks: int = 32,
    queries: int = 5_000,
    schedules: int = 2_000,
    seed: int = 0,
) -> dict:
    from dynamo_tpu.kv_router.indexer import KvIndexer
    from dynamo_tpu.kv_router.protocols import (
        KvCacheEvent,
        KvCacheStoredBlock,
        RouterEvent,
    )
    from dynamo_tpu.kv_router.scheduler import KvScheduler

    rng = random.Random(seed)
    idx = KvIndexer(block_size)

    # -------- ingest: store events, prefix-heavy hash chains
    chains: list[list[int]] = []
    events = []
    per_worker = total_blocks // workers
    ev_id = 0
    for w in range(workers):
        for _ in range(max(1, per_worker // chain_blocks)):
            half = chain_blocks // 2
            if rng.random() < 0.25:
                pid = rng.randrange(50)
                prefix = [
                    hash((pid, i)) & 0x7FFFFFFFFFFF for i in range(half)
                ]
            else:
                prefix = [rng.randrange(1 << 48) for _ in range(half)]
            chain = prefix + [
                rng.randrange(1 << 48) for _ in range(chain_blocks - half)
            ]
            chains.append(chain)
            events.append(
                RouterEvent(
                    w,
                    KvCacheEvent.stored_event(
                        ev_id, None, [KvCacheStoredBlock(h) for h in chain]
                    ),
                )
            )
            ev_id += 1
    t0 = time.perf_counter()
    for ev in events:
        idx.apply_event(ev)
    ingest_s = time.perf_counter() - t0
    stored_blocks = len(events) * chain_blocks

    # -------- query latency on the loaded tree
    lat = []
    for _ in range(queries):
        chain = chains[rng.randrange(len(chains))]
        t = time.perf_counter()
        idx.find_matches(chain)
        lat.append(time.perf_counter() - t)
    lat.sort()

    # -------- scheduler selection on top of real overlaps
    sched = KvScheduler(block_size)
    sched.update_workers(list(range(workers)))
    slat = []
    for i in range(schedules):
        chain = chains[rng.randrange(len(chains))]
        tokens = list(range(len(chain) * block_size))
        overlap = idx.find_matches(chain)
        t = time.perf_counter()
        # the router threads the chain it already computed for the
        # indexer query (router.py find_best_match); measure that path
        sched.schedule(tokens, overlap, request_id=str(i), chain=chain)
        slat.append(time.perf_counter() - t)
        if i % 4 == 3:  # keep the active-set bounded like a live router
            sched.free(str(i - 2))
    slat.sort()

    # -------- worker churn
    t0 = time.perf_counter()
    idx.remove_worker(0)
    remove_ms = (time.perf_counter() - t0) * 1e3

    def pct(xs, p):
        return xs[min(len(xs) - 1, int(p * len(xs)))] * 1e6

    return {
        "workers": workers,
        "stored_blocks": stored_blocks,
        "events_per_s": round(len(events) / ingest_s),
        "blocks_per_s": round(stored_blocks / ingest_s),
        "find_p50_us": round(pct(lat, 0.50), 1),
        "find_p99_us": round(pct(lat, 0.99), 1),
        "schedule_p50_us": round(pct(slat, 0.50), 1),
        "schedule_p99_us": round(pct(slat, 0.99), 1),
        "remove_worker_ms": round(remove_ms, 2),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=64)
    ap.add_argument("--blocks", type=int, default=102_400)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--json", default=None, help="also write result here")
    args = ap.parse_args()
    result = run_bench(
        workers=args.workers,
        total_blocks=args.blocks,
        block_size=args.block_size,
    )
    line = json.dumps(result)
    print(line)
    if args.json:
        with open(args.json, "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
