"""Router scale: performance floors.

Round-4 VERDICT missing item #6: prove the event-driven indexer holds the
reference's design point (events from every block of every request
fleet-wide, indexer.rs:187-860). Full-scale numbers live in
benchmarks/bench_router.py (committed as
benchmarks/router_bench_single.json); this test reruns a reduced
load with floors loose enough for a busy CI machine but tight enough that
an accidental O(n^2) or per-query allocation storm fails loudly.
"""

import gc
import random
import time

from dynamo_tpu.kv_router.indexer import KvIndexer
from dynamo_tpu.kv_router.protocols import (
    KvCacheEvent,
    KvCacheStoredBlock,
    RouterEvent,
)
from dynamo_tpu.kv_router.scheduler import KvScheduler

BS = 16


def _events(workers, chains_per_worker, chain_blocks=32, seed=0):
    rng = random.Random(seed)
    chains, events = [], []
    ev_id = 0
    for w in range(workers):
        for _ in range(chains_per_worker):
            half = chain_blocks // 2
            if rng.random() < 0.25:
                pid = rng.randrange(20)
                prefix = [hash((pid, i)) & 0x7FFFFFFF for i in range(half)]
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
    return chains, events


def test_indexer_scale_floors():
    """Reduced-load floors: 16 workers x ~10k blocks on one event loop.

    Context: the reference's decode exemplar (load_planner.md:56,
    ~51 tok/s/GPU) means 64 workers emit ~200 blocks/s fleet-wide; the
    floor here (20k blocks/s on a quarter of that fleet) is two orders
    above the requirement, while full-scale measurements (160k+ blocks/s,
    find p99 ~55us) are recorded in benchmarks/router_bench_single.json.
    """
    chains, events = _events(workers=16, chains_per_worker=20)
    # best of two trials on a fresh indexer each: mid-suite this test
    # inherits whatever garbage the preceding ~200 tests accumulated,
    # and a GC pass landing inside the timed loop gates on the collector
    # rather than the indexer (noise only ever inflates a sample)
    blocks = len(events) * 32
    rate = 0.0
    for _ in range(2):
        gc.collect()
        idx = KvIndexer(BS)
        t0 = time.perf_counter()
        for ev in events:
            idx.apply_event(ev)
        rate = max(rate, blocks / (time.perf_counter() - t0))
    assert rate > 20_000, f"ingest too slow: {rate:.0f}/s"

    rng = random.Random(2)
    lat = []
    for _ in range(500):
        chain = chains[rng.randrange(len(chains))]
        t = time.perf_counter()
        idx.find_matches(chain)
        lat.append(time.perf_counter() - t)
    lat.sort()
    p99 = lat[int(0.99 * len(lat))]
    assert p99 < 2e-3, f"find_matches p99 {p99*1e6:.0f}us exceeds 2ms"


def test_scheduler_scale_floor():
    """A routed decision (overlap + per-worker potential + softmax pick +
    bookkeeping) must stay under 5ms p99 at 16 workers — the full-scale
    p99 (~0.5ms at 64 workers) is in benchmarks/router_bench_*.json."""
    chains, events = _events(workers=16, chains_per_worker=20)
    idx = KvIndexer(BS)
    for ev in events:
        idx.apply_event(ev)
    sched = KvScheduler(BS)
    sched.update_workers(list(range(16)))
    rng = random.Random(3)
    lat = []
    for i in range(300):
        chain = chains[rng.randrange(len(chains))]
        tokens = list(range(len(chain) * BS))
        overlap = idx.find_matches(chain)
        t = time.perf_counter()
        sched.schedule(tokens, overlap, request_id=str(i), chain=chain)
        lat.append(time.perf_counter() - t)
        if i % 2:
            sched.free(str(i))
    lat.sort()
    p99 = lat[int(0.99 * len(lat))]
    assert p99 < 5e-3, f"schedule p99 {p99*1e6:.0f}us exceeds 5ms"
