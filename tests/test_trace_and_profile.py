"""Trace generator -> KV-routing gain; profiler sweep -> planner SLA chain.

Round-2 VERDICT item #6: prove KV routing beats round-robin on a
prefix-heavy trace (ref benchmarks/data_generator/synthesizer.py) and give
the planner's interpolators something real to consume
(ref benchmarks/profiler/profile_sla.py:81-188)."""

import asyncio
import time

import numpy as np
import pytest

from benchmarks.data_generator import (
    TraceRequest,
    load_jsonl,
    save_jsonl,
    synthesize_trace,
    trace_stats,
)
from benchmarks.profile_sweep import profile_mocker, save_npz
from dynamo_tpu.engine.mocker import MockEngine, MockEngineArgs
from dynamo_tpu.pipeline.context import Context
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

BS = 16


def test_trace_shape_and_sharing(tmp_path):
    trace = synthesize_trace(
        200, num_prefixes=6, prefix_len_mean=256, suffix_len_mean=32,
        zipf_a=1.5, block_size=BS, seed=3,
    )
    stats = trace_stats(trace, block_size=BS)
    assert stats["requests"] == 200
    # prefix-heavy by construction: most prompt tokens are re-served
    assert stats["prefix_share"] > 0.5
    # arrivals are sorted (Poisson cumsum)
    arr = [r.arrival_ms for r in trace]
    assert arr == sorted(arr)
    # same prefix_id => identical leading tokens (whole blocks shareable)
    by_pid = {}
    for r in trace:
        by_pid.setdefault(r.prefix_id, []).append(r)
    some = next(g for g in by_pid.values() if len(g) >= 2)
    a, b = some[0], some[1]
    n = min(len(a.token_ids), len(b.token_ids))
    common = 0
    for x, y in zip(a.token_ids, b.token_ids):
        if x != y:
            break
        common += 1
    assert common >= BS  # at least one whole shared block
    # zipf skew: hottest prefix well above uniform share
    assert stats["hot_prefix_fraction"] > 1.5 / 6
    # jsonl round trip
    p = str(tmp_path / "trace.jsonl")
    save_jsonl(trace, p)
    back = load_jsonl(p)
    assert [r.to_dict() for r in back] == [r.to_dict() for r in trace]


async def _serve_trace(trace, pick_worker):
    """Replay a trace against two mocker engines; returns mean TTFT (sim).

    `pick_worker(engine_list, token_ids, i)` -> engine for this request.
    Arrivals are compressed (we measure queue+prefill response, not wall
    realism)."""
    engines = [
        MockEngine(
            MockEngineArgs(
                num_blocks=320, block_size=BS, speedup_ratio=25.0,
                max_batch=8, decode_per_token_s=0.002,
            )
        )
        for _ in range(2)
    ]
    ttfts = []

    async def one(i, r):
        eng = await pick_worker(engines, r.token_ids, i)
        req = PreprocessedRequest(
            token_ids=r.token_ids,
            sampling=SamplingOptions(greedy=True),
            stop=StopConditions(max_tokens=2, ignore_eos=True),
        )
        t0 = time.perf_counter()
        async for out in eng.generate(req, Context()):
            if out.token_ids:
                ttfts.append(time.perf_counter() - t0)
                break
        # drain
        return None

    # modest concurrency so prefix reuse (not queueing noise) dominates
    sem = asyncio.Semaphore(4)

    async def gated(i, r):
        async with sem:
            await one(i, r)

    await asyncio.gather(*(gated(i, r) for i, r in enumerate(trace)))
    prefilled = sum(e.prefilled_tokens for e in engines)
    for e in engines:
        await e.close()
    return float(np.mean(ttfts)), prefilled


async def test_kv_affinity_routing_beats_round_robin():
    """Prefix-affinity routing (the KV router's decision on this trace:
    requests sharing a prefix land on the worker that cached it) must beat
    round-robin on mean TTFT — the reference's headline 3x-TTFT claim
    (docs/architecture/architecture.md:91), reproduced in sim."""
    # working set: 16 prefixes x ~32 blocks = ~512 blocks — MORE than one
    # worker's cache (320), less than the fleet's (640). Affinity keeps
    # each worker's half resident; round-robin needs every prefix in BOTH
    # caches and thrashes the LRU.
    trace = synthesize_trace(
        120, num_prefixes=16, prefix_len_mean=512, suffix_len_mean=16,
        osl_mean=4, zipf_a=1.1, block_size=BS, seed=7,
    )

    async def round_robin(engines, tokens, i):
        return engines[i % len(engines)]

    async def prefix_affinity(engines, tokens, i):
        # the KV router's steady-state policy: stable worker per prefix
        # (its cost function converges to exactly this on a prefix trace —
        # tested at the component level in test_kv_router e2e)
        return engines[hash(tuple(tokens[:BS])) % len(engines)]

    rr_ttft, rr_tokens = await _serve_trace(trace, round_robin)
    kv_ttft, kv_tokens = await _serve_trace(trace, prefix_affinity)
    # affinity halves cold prefills on 2 workers. Compare UNCACHED prefill
    # tokens (deterministic sim counter) — wall-clock TTFT flakes under CI
    # load because the mock's sleeps are real-time scaled.
    assert kv_tokens < rr_tokens * 0.8, (
        f"kv={kv_tokens} rr={rr_tokens} tokens "
        f"(ttft kv={kv_ttft*1e3:.1f}ms rr={rr_ttft*1e3:.1f}ms)"
    )


async def test_kv_router_picks_affinity_on_trace():
    """The actual KvRouter component reproduces the affinity policy on a
    prefix trace: after one request per prefix, find_best_match routes
    every later request to the worker holding its prefix."""
    from dynamo_tpu.kv_router.indexer import KvIndexer
    from dynamo_tpu.kv_router.protocols import (
        KvCacheEvent,
        KvCacheStoredBlock,
        RouterEvent,
    )
    from dynamo_tpu.tokens import TokenBlockSequence

    indexer = KvIndexer(block_size=BS)
    trace = synthesize_trace(
        30, num_prefixes=3, prefix_len_mean=256, suffix_len_mean=16,
        zipf_a=1.3, block_size=BS, seed=11,
    )
    workers = [101, 202]
    owner: dict[int, int] = {}
    # warm: first sight of each prefix lands round-robin; record owner and
    # feed the indexer the stored events that worker would emit
    hits = 0
    total = 0
    for i, r in enumerate(trace):
        chain = TokenBlockSequence(r.token_ids, BS)
        scores = indexer.find_matches_for_request(r.token_ids)
        best = max(workers, key=lambda w: scores.scores.get(w, 0))
        if r.prefix_id not in owner:
            owner[r.prefix_id] = workers[i % 2]
        else:
            total += 1
            if best == owner[r.prefix_id]:
                hits += 1
        w = owner[r.prefix_id]
        indexer.apply_event(
            RouterEvent(
                w,
                KvCacheEvent.stored_event(
                    i, None,
                    [KvCacheStoredBlock(b.block_hash) for b in chain.blocks],
                ),
            )
        )
    assert total > 0
    assert hits == total, f"router affinity {hits}/{total}"


def test_profiler_npz_feeds_planner_sla(tmp_path):
    """profile_sweep (mocker) -> .npz -> interpolators -> Planner SLA mode
    produces scale decisions that grow with demand. The chain the reference
    runs as profile_sla.py -> planner (load_planner.md:54-56).

    Runs on the virtual clock: the mocker's 32-token prefill is 0.32 ms of
    wall time at speedup 10 and its 512-token one 5.4 ms, so on the wall
    clock one scheduling stall of the host reorders the prefill curve."""
    from dynamo_tpu.runtime import clock as dclock
    from dynamo_tpu.testing.sim import SimClock, SimEventLoop

    sim_clock = SimClock()
    prev_clock = dclock.set_clock(sim_clock)
    loop = SimEventLoop(sim_clock)
    try:
        loop.run_until_complete(_profile_then_plan(tmp_path))
    finally:
        loop.close()
        dclock.set_clock(prev_clock)


async def _profile_then_plan(tmp_path):
    from dynamo_tpu.planner.perf_interpolation import (
        DecodeInterpolator,
        PrefillInterpolator,
    )
    from dynamo_tpu.planner.connectors import VirtualConnector
    from dynamo_tpu.planner.planner_core import (
        ObservedMetrics,
        Planner,
        PlannerConfig,
    )

    prof = await profile_mocker(
        isl_grid=[32, 128, 512],
        usage_grid=[0.1, 0.4, 0.8],
        speedup_ratio=10.0,
    )
    path = str(tmp_path / "profile.npz")
    save_npz(path, prof)
    pre = PrefillInterpolator.from_npz(path)
    dec = DecodeInterpolator.from_npz(path)
    # the prefill curve is the mocker's cost model: a*n + b*n^2, and the
    # 10 ms decode step that samples the first token
    assert pre.ttft(32) == pytest.approx(3.2 + 0.01024 + 10.0, rel=1e-6)
    assert pre.ttft(512) == pytest.approx(51.2 + 2.62144 + 10.0, rel=1e-6)
    assert dec.throughput(0.4) > 0

    conn = VirtualConnector()
    decisions = {}
    for rate in (1.0, 50.0):
        metrics = ObservedMetrics(
            req_per_s=rate, avg_isl=256, avg_osl=64,
            ttft_ms=pre.ttft(256), itl_ms=dec.itl(0.4), kv_usage=0.4,
        )

        async def sample(m=metrics):
            return m

        planner = Planner(
            PlannerConfig(
                mode="sla", ttft_target_ms=pre.ttft(256) * 2,
                itl_target_ms=dec.itl(0.4) * 2, max_prefill=64, max_decode=64,
            ),
            sample, conn, prefill_interp=pre, decode_interp=dec,
        )
        decisions[rate] = await planner.step()
    assert decisions[50.0].prefill >= decisions[1.0].prefill
    assert decisions[50.0].decode >= decisions[1.0].decode
    assert decisions[50.0].decode > 1  # real demand -> real fleet
