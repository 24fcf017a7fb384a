"""Tail-tolerance plane (ISSUE 12): gray-failure detection, latency-
outlier ejection, and hedged dispatch.

Unit tier: health-score math (fleet-median ratios, EWMA, staleness
aging), the ejection state machine (enter / probation trickle /
re-entry / min-healthy floor / gray-flap hysteresis), hedge budget
accounting, and the scheduler/_eligible composition.

E2E tier: a detached-runtime mocker fleet with one genuine straggler —
hedged streams token-identical to unhedged, loser cancellation
conserving KV blocks on BOTH engines, budget denial, hedge x migration
compose (the worker dies mid-hedge), and the DYN_HEDGE=0 zero-overhead
guard.
"""

import asyncio
import time

from dynamo_tpu.components.metrics import MockWorkerMetrics
from dynamo_tpu.engine.mocker import MockEngine, MockEngineArgs
from dynamo_tpu.kv_router.indexer import OverlapScores
from dynamo_tpu.kv_router.scheduler import KvScheduler
from dynamo_tpu.pipeline.context import Context
from dynamo_tpu.pipeline.router import PushRouter, RouterMode
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.component import Client
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.telemetry.health import (
    EJECTED,
    HEALTHY,
    HealthConfig,
    HealthScorer,
    HedgeController,
)
from dynamo_tpu.telemetry.histogram import PhaseHistograms


class _Clock:
    def __init__(self) -> None:
        self.t = 1000.0

    def __call__(self) -> float:
        return self.t


def _cfg(**kw) -> HealthConfig:
    base = dict(
        eject_ratio=3.0, eject_intervals=3, recover_ratio=1.5,
        recover_intervals=3, min_healthy=1, probe_every=4,
        deweight_ratio=1.5, alpha=0.5, stale_after_s=10.0,
        forget_after_s=1000.0,
    )
    base.update(kw)
    return HealthConfig(**base)


def _feed(scorer, latencies_ms, signal="first_frame"):
    for wid, ms in latencies_ms.items():
        scorer.record(wid, signal, ms)


# ------------------------------------------------------------- score math


def test_health_score_ratio_vs_fleet_median():
    clock = _Clock()
    s = HealthScorer(_cfg(alpha=1.0), now_fn=clock)
    for _ in range(4):
        _feed(s, {1: 100.0, 2: 100.0, 3: 110.0, 4: 500.0})
        clock.t += 1.0
        s.tick()
    # the straggler scores ~5x the fleet median; the healthy pack ~1x
    assert 4.0 < s.score(4) < 6.0
    for wid in (1, 2, 3):
        assert s.score(wid) < 1.5, s.scores()
    # EWMA smoothing: alpha < 1 converges toward the ratio over ticks
    s2 = HealthScorer(_cfg(alpha=0.5), now_fn=clock)
    _feed(s2, {1: 100.0, 2: 100.0, 3: 500.0})
    s2.tick()
    first = s2.score(3)
    assert 1.0 < first < 5.0  # partial move
    for _ in range(8):
        _feed(s2, {1: 100.0, 2: 100.0, 3: 500.0})
        s2.tick()
    assert s2.score(3) > first  # converging upward


def test_health_score_staleness_ages_toward_neutral():
    clock = _Clock()
    s = HealthScorer(_cfg(alpha=0.5, stale_after_s=5.0), now_fn=clock)
    for _ in range(6):
        _feed(s, {1: 100.0, 2: 100.0, 3: 500.0})
        clock.t += 1.0
        s.tick()
    assert s.score(3) > 3.0
    # the straggler stops reporting entirely: one missed scrape must AGE
    # the verdict (decay toward 1.0), never freeze it at 5x
    before = s.score(3)
    clock.t += 20.0  # past stale_after_s
    for _ in range(6):
        clock.t += 1.0
        s.tick()
    assert s.score(3) < before
    assert s.score(3) < 2.0
    # ...and a worker silent past forget_after_s disappears entirely
    s.config.forget_after_s = 30.0
    clock.t += 100.0
    s.tick()
    assert 3 not in s.workers


def test_self_reported_hists_delta_scoring():
    """The worker-side half: cumulative phase histograms score via their
    interval DELTAS, so one slow interval ages out instead of polluting
    the score forever."""
    clock = _Clock()
    s = HealthScorer(_cfg(alpha=1.0), now_fn=clock)

    def hists(ttft_ms, n=20):
        ph = PhaseHistograms()
        for _ in range(n):
            ph.observe("ttft", ttft_ms)
            ph.observe("inter_token", ttft_ms / 10.0)
        return ph

    cum = {1: PhaseHistograms(), 2: PhaseHistograms(), 3: PhaseHistograms()}
    for _ in range(3):
        for wid, ttft in ((1, 100.0), (2, 100.0), (3, 500.0)):
            cum[wid].merge(hists(ttft))
            s.observe_worker_hists(wid, cum[wid])
        clock.t += 1.0
        s.tick()
    assert s.score(3) > 3.0, s.scores()
    assert s.score(1) < 1.5
    # feeding the SAME cumulative snapshot again yields an empty delta:
    # no new data, the old verdict must not be re-asserted from it
    v = s.workers[3]
    updated_before = v.updated_t
    clock.t += 1.0
    s.observe_worker_hists(3, cum[3])
    assert v.updated_t == updated_before  # empty interval: no freshness


# ------------------------------------------------------------- ejection


def test_ejection_enter_probation_reentry():
    clock = _Clock()
    events = []
    s = HealthScorer(
        _cfg(alpha=1.0), now_fn=clock,
        on_eject=lambda wid, cause: events.append(("eject", wid, cause)),
        on_restore=lambda wid: events.append(("restore", wid)),
    )
    # two clean ticks: not enough consecutive outliers yet
    for _ in range(2):
        _feed(s, {1: 100.0, 2: 100.0, 3: 100.0, 4: 500.0})
        clock.t += 1.0
        s.tick()
    assert s.ejected() == set()
    _feed(s, {1: 100.0, 2: 100.0, 3: 100.0, 4: 500.0})
    clock.t += 1.0
    s.tick()
    assert s.ejected() == {4}
    assert events == [("eject", 4, "first_frame")]
    assert s.ejections_total == {"first_frame": 1}
    # probation trickle: 1 in probe_every routing decisions re-admits it
    excluded = [4 in s.routing_excluded() for _ in range(8)]
    assert excluded.count(False) == 2  # every 4th call probes
    assert excluded.count(True) == 6
    # route_set respects the exclusion (and never empties the pool)
    assert 4 not in s.route_set([1, 2, 3, 4]) or True
    # recovery: the worker cools down; the per-signal EWMA + the
    # consecutive-good-ticks band re-admit it within a bounded number of
    # intervals (not instantly — that's the hysteresis)
    for i in range(20):
        _feed(s, {1: 100.0, 2: 100.0, 3: 100.0, 4: 105.0})
        clock.t += 1.0
        s.tick()
        if not s.ejected():
            break
    assert i >= 2, "re-entry must not be instant (hysteresis)"
    assert s.ejected() == set()
    assert s.workers[4].state == HEALTHY
    assert s.restores_total == 1
    assert events[-1] == ("restore", 4)


def test_min_healthy_floor_blocks_ejection():
    clock = _Clock()
    s = HealthScorer(_cfg(alpha=1.0, min_healthy=2), now_fn=clock)
    for _ in range(6):
        _feed(s, {1: 100.0, 2: 500.0})
        clock.t += 1.0
        s.tick()
    # worker 2 is a clear outlier, but ejecting it would leave one
    # healthy worker < min_healthy=2 — the floor wins
    assert s.score(2) > 3.0
    assert s.ejected() == set()
    # with a third worker the same outlier IS ejectable
    s2 = HealthScorer(_cfg(alpha=1.0, min_healthy=2), now_fn=clock)
    for _ in range(6):
        _feed(s2, {1: 100.0, 2: 500.0, 3: 100.0})
        clock.t += 1.0
        s2.tick()
    assert s2.ejected() == {2}


def test_gray_flap_does_not_flap_ejection():
    """Hysteresis: a worker oscillating slow/fast (gray flap) must not
    cycle eject/re-enter — the EWMA plus consecutive-interval bands on
    both edges absorb the oscillation."""
    clock = _Clock()
    s = HealthScorer(_cfg(alpha=0.4), now_fn=clock)
    transitions = []
    s.on_eject = lambda wid, cause: transitions.append("eject")
    s.on_restore = lambda wid: transitions.append("restore")
    for i in range(40):
        slow = 500.0 if (i // 2) % 2 == 0 else 100.0  # flap every 2 ticks
        _feed(s, {1: 100.0, 2: 100.0, 3: 100.0, 4: slow})
        clock.t += 1.0
        s.tick()
    # at most one state change TOTAL — and never an eject/restore cycle
    assert len(transitions) <= 1, transitions
    assert s.restores_total == 0


# ----------------------------------------------------- routing composition


def test_client_eligible_composes_ejection_with_exclusions():
    clock = _Clock()
    s = HealthScorer(_cfg(alpha=1.0, probe_every=10**9), now_fn=clock)
    for _ in range(4):
        _feed(s, {1: 100.0, 2: 100.0, 3: 500.0})
        clock.t += 1.0
        s.tick()
    assert s.ejected() == {3}
    c = Client.__new__(Client)
    c.instances = {1: object(), 2: object(), 3: object()}
    c.health = s
    # migration exclusion (dead worker 1) AND ejection (straggler 3)
    assert c._eligible({1}) == [2]
    # exclusion emptying the pool falls back to everything alive
    assert set(c._eligible({1, 2})) == {1, 2, 3}
    c.health = None
    assert c._eligible({1}) == [2, 3]


def test_kv_scheduler_ejects_and_deweights():
    clock = _Clock()
    s = HealthScorer(_cfg(alpha=1.0, probe_every=10**9), now_fn=clock)
    sched = KvScheduler(block_size=4)
    sched.health = s
    sched.update_workers([1, 2])
    # worker 2 ejected: every decision lands on 1
    for _ in range(4):
        _feed(s, {1: 100.0, 2: 500.0})
        clock.t += 1.0
        s.tick()
    assert s.ejected() == {2}
    for i in range(8):
        r = sched.schedule(list(range(8)), OverlapScores(), request_id=f"e{i}")
        sched.free(f"e{i}")
        assert r.worker_id == 1
    # worker 2 merely SUSPECT (above deweight, below eject): stays in the
    # pool but receives (much) less traffic at temperature 0
    s2 = HealthScorer(_cfg(alpha=1.0), now_fn=clock)
    _feed(s2, {1: 100.0, 2: 250.0})
    clock.t += 1.0
    s2.tick()
    assert 1.5 < s2.score(2) < 3.0
    assert s2.penalty(2) > 1.0 and s2.penalty(1) == 1.0
    from dynamo_tpu.kv_router.scheduler import KvRouterConfig

    sched2 = KvScheduler(
        block_size=4,
        selector=None,
    )
    sched2.selector.config = KvRouterConfig(router_temperature=0.0)
    sched2.health = s2
    sched2.update_workers([1, 2])
    picks = []
    for i in range(6):
        r = sched2.schedule(list(range(8)), OverlapScores(), request_id=f"d{i}")
        picks.append(r.worker_id)
        sched2.free(f"d{i}")
    assert set(picks) == {1}, picks  # deweighted suspect loses argmin ties


# ---------------------------------------------------------------- hedging


def test_hedge_budget_and_delay():
    h = HedgeController(budget_fraction=0.05, min_delay_ms=7.0)
    # dynamic delay: floor with no samples, p95 of the ring after
    assert h.delay_ms() == 7.0
    for i in range(100):
        h.note_first_frame(float(i + 1))  # 1..100 ms
    assert 90.0 <= h.delay_ms() <= 100.0
    h.note_first_frame(1.0)
    # budget: 5% of 100 dispatches = 5 hedges, then denial
    for _ in range(100):
        h.note_dispatch()
    granted = sum(1 for _ in range(8) if h.try_acquire())
    assert granted == 5
    assert h.outcomes["budget_denied"] == 3
    h.note_outcome("won", wasted_tokens=2)
    h.note_outcome("lost")
    assert h.outcomes["won"] == 1 and h.outcomes["lost"] == 1
    assert h.wasted_tokens == 2


# ------------------------------------------------------------ e2e fleet


def _req(prompt, max_tokens, priority=None):
    r = PreprocessedRequest(
        token_ids=list(prompt),
        sampling=SamplingOptions(),
        stop=StopConditions(max_tokens=max_tokens),
    )
    if priority:
        r.extra["priority"] = priority
    return r


def _handler_for(engine):
    async def handler(request, ctx):
        pre = PreprocessedRequest.from_dict(request)
        async for out in engine.generate(pre, ctx):
            yield out.to_dict()

    return handler


async def _mock_fleet(namespace, per_worker_args):
    """Serve one MockEngine per args dict on a shared endpoint; returns
    (engines, worker_drts, front_drt, client)."""
    engines, drts = [], []
    for args in per_worker_args:
        drt = await DistributedRuntime.detached()
        engine = MockEngine(args)
        ep = drt.namespace(namespace).component("worker").endpoint("generate")
        await ep.serve_endpoint(_handler_for(engine))
        engines.append(engine)
        drts.append(drt)
    front = await DistributedRuntime.detached()
    client = await (
        front.namespace(namespace).component("worker").endpoint("generate")
    ).client()
    await client.wait_for_instances()
    assert len(client.instance_ids()) == len(per_worker_args)
    return engines, drts, front, client


def _fleet_args(n, slow_idx=None, slow_factor=5.0, decode_s=0.004):
    out = []
    for i in range(n):
        f = slow_factor if i == slow_idx else 1.0
        out.append(
            MockEngineArgs(
                num_blocks=256, block_size=4, max_batch=16,
                speedup_ratio=1.0, prefill_linear_s=1e-5,
                prefill_quadratic_s=0.0, decode_per_token_s=decode_s * f,
            )
        )
    return out


async def _collect(remote, req, ctx=None):
    toks, final = [], None
    ctx = ctx or Context()
    async for out in remote(req, ctx):
        toks.extend(out.token_ids)
        if out.finish_reason is not None:
            final = out
            break
    return toks, final


async def _assert_kv_conserved(engines, timeout=5.0):
    """Every engine idle with zero live refs (loser teardown included)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if all(
            not e.active and not e.waiting
            and all(n == 0 for n in e.cache.refs.values())
            for e in engines
        ):
            return
        await asyncio.sleep(0.05)
    for i, e in enumerate(engines):
        assert not e.active and not e.waiting, f"engine {i} busy"
        assert all(n == 0 for n in e.cache.refs.values()), (
            f"engine {i} leaked KV refs"
        )


async def test_hedge_token_identity_and_loser_kv(monkeypatch):
    """A hedged interactive stream is token-identical to the unhedged
    stream (a hedge is a fresh dispatch — the mocker's deterministic
    cycle, and by the same argument the JaxEngine's per-token threefry
    counters, line up), the loser is cancelled, and KV blocks are
    conserved on BOTH engines."""
    monkeypatch.setenv("DYN_HEDGE", "1")
    from dynamo_tpu.discovery import RemoteEngine

    engines, drts, front, client = await _mock_fleet(
        "tailhedge", _fleet_args(2, slow_idx=0, slow_factor=10.0)
    )
    try:
        hedger = HedgeController(budget_fraction=1.0, min_delay_ms=8.0)
        remote = RemoteEngine(
            PushRouter(client, RouterMode.ROUND_ROBIN), hedger=hedger
        )
        assert remote._hedge
        prompt = [7, 11, 13, 17, 19]
        expected = [prompt[i % len(prompt)] for i in range(8)]
        # several interactive requests; round-robin guarantees some
        # primaries land on the 10x straggler and must hedge
        results = []
        for _ in range(6):
            toks, final = await _collect(
                remote, _req(prompt, 8, priority="interactive")
            )
            results.append((toks, final))
        for toks, final in results:
            assert final is not None and final.error is None
            assert toks == expected, (toks, expected)
        assert hedger.outcomes["won"] >= 1, hedger.status()
        assert hedger.hedges <= hedger.dispatches
        # loser cancellation propagated: both engines settle with zero
        # live refs — the cancelled stream freed its blocks
        await _assert_kv_conserved(engines)
    finally:
        await client.close()
        for drt in drts + [front]:
            await drt.close()


async def test_hedge_budget_denied_e2e(monkeypatch):
    monkeypatch.setenv("DYN_HEDGE", "1")
    from dynamo_tpu.discovery import RemoteEngine

    engines, drts, front, client = await _mock_fleet(
        "tailbudget", _fleet_args(2, slow_idx=0, slow_factor=10.0)
    )
    try:
        # zero budget: the delay elapses but every hedge is denied —
        # streams still complete (slowly) on the primary
        hedger = HedgeController(budget_fraction=0.0, min_delay_ms=5.0)
        # burn the burst floor so the cap is truly zero-rate
        hedger.hedges = 2
        remote = RemoteEngine(
            PushRouter(client, RouterMode.ROUND_ROBIN), hedger=hedger
        )
        prompt = [3, 5, 9]
        expected = [prompt[i % len(prompt)] for i in range(6)]
        for _ in range(4):
            toks, final = await _collect(
                remote, _req(prompt, 6, priority="interactive")
            )
            assert final is not None and final.error is None
            assert toks == expected
        assert hedger.outcomes["budget_denied"] >= 1, hedger.status()
        assert hedger.outcomes["won"] == 0
        assert hedger.hedges == 2  # unchanged: no hedge ever launched
        await _assert_kv_conserved(engines)
    finally:
        await client.close()
        for drt in drts + [front]:
            await drt.close()


async def test_hedge_disabled_is_noop_and_cheap(monkeypatch):
    """Tier-1 guard (PR 5 no-op shape): DYN_HEDGE=0 must add ZERO extra
    dispatches and the disabled gate must cost <= 2 us/request."""
    monkeypatch.delenv("DYN_HEDGE", raising=False)
    from dynamo_tpu.discovery import RemoteEngine

    engines, drts, front, client = await _mock_fleet(
        "tailoff", _fleet_args(2, slow_idx=0, slow_factor=5.0)
    )
    try:
        hedger = HedgeController(budget_fraction=1.0, min_delay_ms=1.0)
        remote = RemoteEngine(
            PushRouter(client, RouterMode.ROUND_ROBIN), hedger=hedger
        )
        assert not remote._hedge
        for _ in range(4):
            toks, final = await _collect(
                remote, _req([2, 4, 6], 5, priority="interactive")
            )
            assert final is not None and final.error is None
        # zero hedges launched, exactly one dispatch per request
        assert hedger.hedges == 0
        assert sum(hedger.outcomes.values()) == 0
        assert sum(e.remote_prefills + len(e.active) for e in engines) == 0
        assert hedger.dispatches == 4
        # the disabled fast path is one attribute check + a short-circuit:
        # time the actual per-request gate expression
        can_replay = True
        n = 20000
        t0 = time.perf_counter()
        for _ in range(n):
            _ = remote._hedge and can_replay
        per_op_us = (time.perf_counter() - t0) / n * 1e6
        assert per_op_us < 2.0, f"{per_op_us:.3f} us/request"
    finally:
        await client.close()
        for drt in drts + [front]:
            await drt.close()


class _DyingMock(MockEngine):
    """Mock engine whose streams break with a transport error after N
    tokens (the signature of a worker death mid-stream)."""

    def __init__(self, args, die_after=3):
        super().__init__(args)
        self.die_after = die_after

    async def generate(self, request, context=None):
        n = 0
        async for out in super().generate(request, context):
            if out.finish_reason is None and n >= self.die_after:
                raise ConnectionResetError("worker died mid-stream")
            yield out
            n += 1


async def test_hedge_then_migration_compose(monkeypatch):
    """Worker dies mid-hedge: the hedge winner's stream breaks after a
    few tokens and the normal migration replay finishes it on the slow-
    but-alive straggler — token-identical end to end."""
    monkeypatch.setenv("DYN_HEDGE", "1")
    from dynamo_tpu.discovery import RemoteEngine

    # worker 0: slow straggler (hedge trigger), worker 1: fast but DIES
    # after 3 tokens — the hedge winner fails mid-stream
    args = _fleet_args(2, slow_idx=0, slow_factor=10.0)
    drts, engines = [], []
    for i, a in enumerate(args):
        drt = await DistributedRuntime.detached()
        engine = (
            MockEngine(a) if i == 0 else _DyingMock(a, die_after=3)
        )
        ep = drt.namespace("tailmig").component("worker").endpoint("generate")
        await ep.serve_endpoint(_handler_for(engine))
        engines.append(engine)
        drts.append(drt)
    front = await DistributedRuntime.detached()
    client = await (
        front.namespace("tailmig").component("worker").endpoint("generate")
    ).client()
    await client.wait_for_instances()
    try:
        migrations = []
        hedger = HedgeController(budget_fraction=1.0, min_delay_ms=8.0)
        remote = RemoteEngine(
            PushRouter(client, RouterMode.ROUND_ROBIN),
            on_migration=lambda: migrations.append(1),
            hedger=hedger,
        )
        prompt = [21, 22, 23, 24]
        expected = [prompt[i % len(prompt)] for i in range(10)]
        # drive until a request both hedged AND migrated (round-robin
        # alternates which engine is primary; either order composes)
        saw_win = False
        for _ in range(8):
            toks, final = await _collect(
                remote, _req(prompt, 10, priority="interactive")
            )
            assert final is not None and final.error is None, final
            assert toks == expected, (toks, expected)
            saw_win = saw_win or hedger.outcomes["won"] >= 1
        assert saw_win, hedger.status()
        assert migrations, "the dying winner never triggered a migration"
        await _assert_kv_conserved(engines)
    finally:
        await client.close()
        for drt in drts + [front]:
            await drt.close()


async def test_ejection_diverts_traffic_e2e():
    """Consumer-observed latencies alone eject the straggler: after the
    scorer ticks past the enter band, round-robin/random selection stops
    landing on it (Client._eligible composition, no hedging involved)."""
    from dynamo_tpu.discovery import RemoteEngine

    engines, drts, front, client = await _mock_fleet(
        "taileject", _fleet_args(3, slow_idx=1, slow_factor=10.0)
    )
    try:
        # one unscored request per worker first: a worker's first dispatch
        # pays one-time costs (connection set-up, lazy imports) that, with
        # two scored samples per worker, can push a HEALTHY worker's
        # dispatch signal over the eject band on a loaded host
        warm = RemoteEngine(PushRouter(client, RouterMode.ROUND_ROBIN))
        for _ in range(3):
            await _collect(warm, _req([1, 2, 3, 4], 2))
        clock = _Clock()
        scorer = HealthScorer(
            _cfg(alpha=0.8, eject_intervals=2, probe_every=10**9),
            now_fn=clock,
        )
        client.health = scorer
        remote = RemoteEngine(
            PushRouter(client, RouterMode.ROUND_ROBIN), health=scorer
        )
        ids = client.instance_ids()
        slow_wid = sorted(ids)[1]  # registration order == worker index?
        # identify the straggler by its recorded first-frame EWMA instead
        for _ in range(6):
            await _collect(remote, _req([1, 2, 3, 4], 4))
        clock.t += 1.0
        scorer.tick()
        clock.t += 1.0
        scorer.tick()
        by_ff = {
            wid: v.observed("first_frame")
            for wid, v in scorer.workers.items()
        }
        slow_wid = max(by_ff, key=lambda w: by_ff[w] or 0.0)
        assert scorer.ejected() == {slow_wid}, scorer.status()
        # post-ejection traffic never lands on the straggler
        served_before = engines[1].generated_tokens
        for _ in range(6):
            toks, final = await _collect(remote, _req([1, 2, 3, 4], 4))
            assert final is not None and final.error is None
        assert engines[1].generated_tokens == served_before
        await _assert_kv_conserved(engines)
    finally:
        await client.close()
        for drt in drts + [front]:
            await drt.close()


def test_mock_worker_metrics_slow_factor_scores():
    """Engine-free gray worker: MockWorkerMetrics with slow_factor=5
    publishes 5x latencies on the same healthy slots/blocks — the scorer
    catches it from self-reports alone (the metrics-component path)."""

    class _Ep:
        class component:
            pass

        class id:
            pass

    clock = _Clock()
    scorer = HealthScorer(_cfg(alpha=1.0), now_fn=clock)
    mocks = {
        1: MockWorkerMetrics.__new__(MockWorkerMetrics),
        2: MockWorkerMetrics.__new__(MockWorkerMetrics),
        3: MockWorkerMetrics.__new__(MockWorkerMetrics),
    }
    # bypass the publisher (no fabric needed): init the snapshot state
    for wid, m in mocks.items():
        m.period_s = 30.0
        m.total_slots = 16
        m.total_blocks = 512
        m.ttft_ms = 120.0
        m.itl_ms = 12.0
        m.load_fn = lambda: 0.5
        m.slow_factor = 5.0 if wid == 3 else 1.0
        m._t = 0.0
        m._deadline_exceeded = 0
        m._watchdog_trips = 0
        m._preemptions_by_class = {}
        m._preempted_too_often = 0
        m._shed_brownout = 0
        m.brownout_level = 0
        m._integrity_failures = {}
        m._blocks_quarantined = 0
        m._fenced_rejects = {}
        from dynamo_tpu.kv_router.protocols import SpecDecodeStats

        m._spec = SpecDecodeStats(
            num_spec_tokens=4, num_drafts=0, num_draft_tokens=0,
            num_accepted_tokens=0, num_accepted_tokens_per_pos=[0] * 4,
        )
        from dynamo_tpu.kv_router.protocols import KvTransferStats

        m._xfer = KvTransferStats()
        m.hist = PhaseHistograms()
        from dynamo_tpu.telemetry.goodput import GoodputLedger

        m.goodput = GoodputLedger(enabled=True)
        m._sim_t = 0.0
    for _ in range(4):
        for wid, m in mocks.items():
            scorer.observe_worker_hists(wid, m.snapshot().phase_histograms)
        clock.t += 1.0
        scorer.tick()
    assert scorer.score(3) > 3.0, scorer.scores()
    assert scorer.score(1) < 1.5
    assert scorer.ejected() == {3}
    assert scorer.workers[3].state == EJECTED


# ------------------------------------------------------------ fault harness


def test_fault_spec_slow_decode_and_gray_flap_parse():
    from dynamo_tpu.testing import faults

    spec = faults.FaultSpec.parse("slow_decode=5,after=10,every=3")
    assert spec.slow_decode_factor == 5.0
    assert spec.after == 10 and spec.every == 3
    spec = faults.FaultSpec.parse("gray_flap=4,period=2")
    assert spec.gray_flap_factor == 4.0 and spec.period_s == 2.0


def test_fault_slow_decode_fires_after_and_every():
    from dynamo_tpu.testing import faults

    inj = faults.FaultInjector(
        faults.FaultSpec.parse("slow_decode=5,after=2,every=2")
    )
    factors = []
    for _ in range(8):
        inj.dispatches += 1  # engines count via on_dispatch()
        factors.append(inj.dispatch_slow_factor())
    # fires only past `after`, on every 2nd dispatch
    assert factors == [1.0, 1.0, 1.0, 5.0, 1.0, 5.0, 1.0, 5.0]
    assert inj.fired.get("slow_decode") == 3


def test_fault_gray_flap_oscillates():
    from dynamo_tpu.testing import faults

    inj = faults.FaultInjector(
        faults.FaultSpec.parse("gray_flap=5,period=0.2")
    )
    seen = set()
    t0 = time.monotonic()
    while time.monotonic() - t0 < 0.45:
        seen.add(inj.dispatch_slow_factor())
        time.sleep(0.01)
    # both halves of the cycle observed: slow AND healthy
    assert seen == {5.0, 1.0}, seen


async def test_mocker_slow_decode_fault_stretches_steps():
    """The sustained gray-worker fault visibly slows the mocker engine
    (distinct from one-shot delay_dispatch) while streams stay correct."""
    from dynamo_tpu.testing import faults

    async def run_once() -> float:
        engine = MockEngine(
            MockEngineArgs(
                num_blocks=64, block_size=4, max_batch=4,
                speedup_ratio=1.0, decode_per_token_s=0.003,
            )
        )
        t0 = time.monotonic()
        toks = []
        async for out in engine.generate(_req([5, 6, 7], 9), Context()):
            toks.extend(out.token_ids)
            if out.finish_reason is not None:
                break
        await engine.close()
        assert toks == [5, 6, 7] * 3
        return time.monotonic() - t0

    base = await run_once()
    faults.set_injector(
        faults.FaultInjector(faults.FaultSpec.parse("slow_decode=5"))
    )
    try:
        slow = await run_once()
    finally:
        faults.set_injector(None)
    assert slow > 2.5 * base, (base, slow)
