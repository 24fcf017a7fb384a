"""Goodput ledger (ISSUE 14): per-device-step efficiency accounting, the
token-waste taxonomy, and recompile forensics.

Covers ledger bounding under label churn, phase-bubble accounting, wire
roundtrip + merge associativity (the fleet-aggregation contract), waste
attribution for every taxonomy cause on the mock engine (deadline both
directly and driven via the DYN_FAULT slow_decode gray fault), recompile
forensics units (detector thresholds, WARN naming the offending shape,
prebake manifest roundtrip), frontend /metrics + /debug/goodput with the
hedge_loser overlay, fleet-vs-direct /debug/goodput agreement within the
histogram's documented error, and the always-on overhead guard."""

import asyncio
import gc
import json
import logging
import math
import random
import time

import aiohttp
import pytest
from prometheus_client import generate_latest

from dynamo_tpu.components.metrics import (
    MetricsComponent,
    MockWorkerMetrics,
    goodput_families,
)
from dynamo_tpu.engine.mocker import MockEngine, MockEngineArgs
from dynamo_tpu.entrypoint.inputs import EngineConfig, run_http
from dynamo_tpu.http.metrics import ServiceMetrics
from dynamo_tpu.kv_router.protocols import ForwardPassMetrics
from dynamo_tpu.kv_router.publisher import WorkerMetricsPublisher
from dynamo_tpu.pipeline.context import Context
from dynamo_tpu.protocols.common import (
    FinishReason,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.protocols import EndpointId
from dynamo_tpu.telemetry.goodput import (
    MAX_LABELS,
    WASTE_CAUSES,
    GoodputLedger,
    GoodputStats,
    RecompileDetector,
    enabled_from_env,
    load_prebaked_labels,
    normalize_label,
    write_prebake_manifest,
)
from dynamo_tpu.telemetry.health import HedgeController
from dynamo_tpu.telemetry.histogram import QUANTILE_REL_ERROR
from dynamo_tpu.testing import faults

from tests.util import make_test_mdc


def req(prompt, max_tokens=8, priority=None, ignore_eos=False, **sampling):
    pre = PreprocessedRequest(
        token_ids=list(prompt),
        sampling=SamplingOptions(**sampling) if sampling else SamplingOptions(),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=ignore_eos),
    )
    if priority is not None:
        pre.extra["priority"] = priority
    return pre


async def collect(engine, request, ctx=None):
    toks, final = [], None
    async for out in engine.generate(request, ctx or Context()):
        toks.extend(out.token_ids)
        if out.finish_reason is not None:
            final = out
    return toks, final


# ---------------------------------------------------------- ledger units


def test_ledger_bounded_under_label_churn():
    """A label accidentally built from a shape must never grow the ledger
    unbounded: every label-keyed dict is capped at MAX_LABELS while the
    scalar totals keep counting."""
    gp = GoodputLedger(enabled=True)
    for i in range(100):
        gp.record_step(f"decode@bs{i}", 0.004)
        gp.record_compile(f"decode@bs{i}", 1.0 + i)
        gp.record_recompile(f"decode@bs{i}", "shape_miss", shape=f"bs={i}")
    assert gp.steps_total == 100
    assert len(gp.step_hists.phases) <= MAX_LABELS
    assert len(gp.compile_s_by_label) <= MAX_LABELS
    assert len(gp.recompiles) <= MAX_LABELS
    # known labels keep recording past the cap
    gp.record_step("decode@bs0", 0.004)
    assert gp.step_hists.phases["decode@bs0"].count == 2


def test_bubble_accounting_and_mark_idle():
    """The gap between one dispatch's end and the next dispatch's start is
    a phase bubble — unless the engine marked itself idle in between."""
    gp = GoodputLedger(enabled=True)
    gp.record_step("prefill", 0.010, t_start=100.000)  # ends 100.010
    gp.record_step("decode", 0.010, t_start=100.015)  # 5 ms bubble
    gp.record_step("decode", 0.010, t_start=100.025)  # back-to-back: none
    gp.mark_idle()
    gp.record_step("prefill", 0.010, t_start=300.0)  # idle, not a bubble
    assert gp.bubble_s_total == pytest.approx(0.005, abs=1e-9)


def test_disabled_ledger_is_inert(monkeypatch):
    gp = GoodputLedger(enabled=False)
    gp.record_step("decode", 0.004, lanes=3, capacity=8, prefill_tokens=64)
    gp.record_decode_tokens(10)
    gp.record_waste("spec_rejected", 5)
    gp.record_compile("decode", 2.0)
    gp.record_recompile("decode", "shape_miss")
    assert gp.total_events() == 0
    assert gp.decode_tokens == 0 and gp.occupancy == 0.0
    # the env knob the constructor reads
    monkeypatch.setenv("DYN_GOODPUT", "0")
    assert not enabled_from_env()
    assert not GoodputLedger().enabled
    monkeypatch.setenv("DYN_GOODPUT", "1")
    assert enabled_from_env()
    monkeypatch.delenv("DYN_GOODPUT")
    assert enabled_from_env()  # default: always on


def _synthetic_stats(seed: int) -> GoodputStats:
    rng = random.Random(seed)
    gp = GoodputLedger(enabled=True)
    t = 100.0
    for _ in range(50 + seed * 13):
        dur = rng.lognormvariate(-4.0 + 0.3 * seed, 0.5)
        gp.record_step(
            rng.choice(("prefill", "decode", "decode_multi")),
            dur,
            lanes=rng.randrange(0, 9),
            capacity=8,
            prefill_tokens=rng.randrange(0, 256),
            t_start=t,
        )
        t += dur + rng.random() * 0.002
    gp.record_decode_tokens(seed * 100 + 7)
    for cause in WASTE_CAUSES:
        gp.record_waste(cause, rng.randrange(0, 50))
    gp.record_compile("decode", 10.0 + seed)
    if seed % 2:
        gp.record_recompile("decode", "shape_miss", shape="lanes=9")
    return gp


def _assert_stats_equal(a: GoodputStats, b: GoodputStats) -> None:
    da, db = a.to_dict(), b.to_dict()
    for key in ("st", "ls", "lc", "pt", "dt", "w", "rc", "sh"):
        assert da[key] == db[key], key
    assert da["bub"] == pytest.approx(db["bub"], rel=1e-9)
    for lbl in set(da["cs"]) | set(db["cs"]):
        assert da["cs"][lbl] == pytest.approx(db["cs"][lbl], rel=1e-9), lbl


def test_wire_roundtrip_preserves_summary():
    gp = _synthetic_stats(2)
    wire = json.loads(json.dumps(gp.to_dict()))  # JSON-safe wire form
    back = GoodputStats.from_dict(wire)
    _assert_stats_equal(gp, back)
    assert back.summary() == gp.summary()


def test_merge_associative_and_commutative():
    """The fleet-aggregation contract: merge order must not matter, so
    (a+b)+c == a+(b+c) and a+b == b+a field-for-field."""
    a, b, c = (_synthetic_stats(s) for s in (0, 1, 2))

    def fold(*parts: GoodputStats) -> GoodputStats:
        out = GoodputStats()
        for p in parts:
            out.merge(p.copy())
        return out

    left = fold(fold(a, b), c)
    right = fold(a, fold(b, c))
    _assert_stats_equal(left, right)
    _assert_stats_equal(fold(a, b), fold(b, a))
    # merged totals are the sums; compile time is the per-label max
    assert left.steps_total == a.steps_total + b.steps_total + c.steps_total
    assert left.compile_s_by_label["decode"] == 12.0


# --------------------------------------------------- recompile forensics


def test_recompile_detector_thresholds(monkeypatch):
    det = RecompileDetector(min_s=0.2, factor=10.0)
    assert det.is_recompile(2.5, 0.004)  # 625x the EMA, over the floor
    assert not det.is_recompile(0.03, 0.002)  # 15x but under the floor
    assert not det.is_recompile(0.5, 0.2)  # big step, only 2.5x EMA
    monkeypatch.setenv("DYN_RECOMPILE_MIN_S", "1.5")
    monkeypatch.setenv("DYN_RECOMPILE_FACTOR", "4")
    env_det = RecompileDetector()
    assert env_det.min_s == 1.5 and env_det.factor == 4.0


def test_recompile_warn_names_offending_shape(caplog):
    gp = GoodputLedger(enabled=True)
    with caplog.at_level(logging.WARNING, logger="dynamo_tpu.telemetry.goodput"):
        gp.record_recompile("decode", "shape_miss", shape="lanes=9,tokens=0")
    assert gp.recompiles == {"decode|shape_miss": 1}
    assert any(
        "decode" in r.getMessage() and "lanes=9,tokens=0" in r.getMessage()
        for r in caplog.records
    ), caplog.text


def test_prebake_manifest_roundtrip(tmp_path):
    """tools/prebake_cache.py writes per-shape program labels; the engine
    reads back base dispatch labels (prebake_miss attribution set)."""
    assert normalize_label("prefill@2048") == "prefill"
    assert normalize_label("decode_eos") == "decode"
    assert normalize_label("decode_multi@H4") == "decode_multi"
    programs = [
        ("prefill@512", 3.1),
        ("prefill@2048", 6.0),
        ("decode", 11.2),
        ("decode_eos", 10.9),
        ("decode_multi@H4", 31.0),
    ]
    path = write_prebake_manifest(str(tmp_path), programs)
    assert path is not None
    assert load_prebaked_labels(str(tmp_path)) == frozenset(
        {"prefill", "decode", "decode_multi"}
    )
    doc = json.loads((tmp_path / "prebake_manifest.json").read_text())
    assert doc["programs"] == [[lbl, s] for lbl, s in programs]
    # missing / unreadable manifests fail closed (no prebake attribution)
    assert load_prebaked_labels(str(tmp_path / "nope")) == frozenset()
    assert load_prebaked_labels(None) == frozenset()


# ------------------------------------------- waste attribution (mocker)


async def test_mocker_step_accounting():
    """Plain run: prefill/decode steps land in the per-label histograms,
    token throughput and occupancy are exact."""
    engine = MockEngine(MockEngineArgs(speedup_ratio=1000.0))
    toks, final = await collect(engine, req(list(range(2, 14)), max_tokens=5))
    assert final.finish_reason is FinishReason.LENGTH
    gp = engine.stats()["goodput"]
    assert gp.step_hists.phases["prefill"].count >= 1
    assert gp.step_hists.phases["decode"].count == 5
    assert gp.prefill_tokens == 12
    assert gp.decode_tokens == 5
    # single lane of a 64-slot batch: occupancy is exactly 1/64
    assert gp.occupancy == pytest.approx(1 / 64)
    assert gp.wasted_total() == 0
    await engine.close()


async def test_mocker_deadline_partial_waste():
    """Every token generated before the deadline expired is attributed to
    deadline_partial — the stream's partial output is discarded."""
    engine = MockEngine(
        MockEngineArgs(speedup_ratio=1.0, decode_per_token_s=0.02)
    )
    ctx = Context()
    ctx.set_deadline_ms(120)
    toks, final = await asyncio.wait_for(
        collect(engine, req([1, 2, 3, 4], max_tokens=500), ctx), timeout=10
    )
    assert final.error["code"] == "deadline_exceeded"
    gp = engine.stats()["goodput"]
    assert 0 < len(toks) < 500
    assert gp.waste_by_cause["deadline_partial"] == len(toks)
    await engine.close()


async def test_mocker_deadline_waste_via_dyn_fault_slow_decode():
    """DYN_FAULT-driven attribution: the sustained slow_decode gray fault
    stretches simulated steps until a mid-stream deadline expiry."""
    faults.set_injector(
        faults.FaultInjector(faults.FaultSpec.parse("slow_decode=200"))
    )
    try:
        # nominal step is 10 us real (0.01 s sim at 1000x): far inside a
        # 150 ms deadline until the fault multiplies it to 2 ms
        engine = MockEngine(MockEngineArgs(speedup_ratio=1000.0))
        ctx = Context()
        ctx.set_deadline_ms(150)
        toks, final = await asyncio.wait_for(
            collect(engine, req([5, 6, 7], max_tokens=2000), ctx), timeout=10
        )
        assert final.error["code"] == "deadline_exceeded"
        gp = engine.stats()["goodput"]
        assert gp.waste_by_cause["deadline_partial"] == len(toks) > 0
        await engine.close()
    finally:
        faults.set_injector(None)


async def test_mocker_migration_replay_waste():
    """An in-flight migration resume re-prefills the tokens the dead
    worker already streamed — exactly the replayed tail is waste."""
    engine = MockEngine()
    prompt = [7, 3, 9, 4, 1]
    baseline, _ = await collect(engine, req(prompt, max_tokens=12))
    assert engine.stats()["goodput"].wasted_total() == 0
    cut = 5
    resumed = req(prompt + baseline[:cut], max_tokens=12)
    resumed.extra["resume_prompt_len"] = len(prompt)
    tail, final2 = await collect(engine, resumed)
    assert tail == baseline[cut:]
    assert engine.stats()["goodput"].waste_by_cause["migration_replay"] == cut
    await engine.close()


async def test_mocker_preempt_replay_waste():
    """A preemption discards the victim's computed KV (prompt + generated
    so far); all of it is preempt_replay waste."""
    engine = MockEngine(
        MockEngineArgs(
            num_blocks=12, block_size=4, max_batch=4, speedup_ratio=500.0,
            watermark=0.0, preempt_backoff_ms=1.0,
        )
    )
    bulk_task = asyncio.ensure_future(
        collect(engine, req(list(range(1, 9)), max_tokens=30,
                            priority="bulk"))
    )
    deadline = time.monotonic() + 10.0
    while not any(
        s.priority == "bulk" and 1 <= s.generated <= 8
        for s in engine.active
    ):
        assert time.monotonic() < deadline, "bulk never started decoding"
        assert not bulk_task.done(), "bulk finished before pressure built"
        await asyncio.sleep(0.0005)
    inter_task = asyncio.ensure_future(
        collect(engine, req(list(range(40, 48)), max_tokens=30,
                            priority="interactive"))
    )
    streams = await asyncio.wait_for(
        asyncio.gather(bulk_task, inter_task), timeout=30
    )
    gp = engine.stats()["goodput"]
    n_preempt = sum(engine.preemptions_by_class.values())
    assert n_preempt >= 1
    # the token whose block found no room is streamed before anyone is
    # preempted for it: both streams keep their requested count
    for (toks, final), first in zip(streams, (1, 40)):
        assert final.finish_reason is FinishReason.LENGTH
        assert toks == [first + i % 8 for i in range(30)]
    # each preemption wasted at least the victim's 8-token prompt
    assert gp.waste_by_cause["preempt_replay"] >= 8 * n_preempt
    await engine.close()


async def test_mocker_cancelled_partial_waste():
    """A consumer disconnect mid-stream attributes the partial output to
    cancelled_partial (the engine-side view of a hedge loser too)."""
    engine = MockEngine(
        MockEngineArgs(speedup_ratio=1.0, decode_per_token_s=0.005)
    )
    ctx = Context()
    task = asyncio.ensure_future(
        collect(engine, req([9, 8, 7], max_tokens=1000), ctx)
    )
    deadline = time.monotonic() + 10.0
    while engine.stats()["goodput"].decode_tokens < 3:
        assert time.monotonic() < deadline, "mocker never decoded"
        await asyncio.sleep(0.002)
    ctx.stop_generating()
    toks, final = await asyncio.wait_for(task, timeout=10)
    assert final.finish_reason is FinishReason.CANCELLED
    gp = engine.stats()["goodput"]
    assert gp.waste_by_cause["cancelled_partial"] == len(toks) >= 3
    await engine.close()


# ------------------------------------------------- frontend (hedge side)


def test_frontend_attach_goodput_hedge_overlay():
    """hedge_loser is frontend-attributed: the HedgeController's wasted
    tokens overlay the engine ledger's taxonomy in the shared families."""
    metrics = ServiceMetrics()
    gp = GoodputLedger(enabled=True)
    gp.record_step("decode", 0.004, lanes=3, capacity=8)
    gp.record_waste("cancelled_partial", 16)
    hedger = HedgeController()
    hedger.wasted_tokens = 7
    metrics.attach_goodput({"goodput": gp}, hedger)
    metrics.attach_goodput({"goodput": gp}, hedger)  # attach-once guard
    text = generate_latest(metrics.registry).decode()
    assert 'dyn_llm_tokens_wasted_total{cause="hedge_loser"} 7.0' in text
    assert 'dyn_llm_tokens_wasted_total{cause="cancelled_partial"} 16.0' in text
    # zero-valued causes still export (stable series, no label churn)
    for cause in WASTE_CAUSES:
        assert f'cause="{cause}"' in text, cause
    assert 'dyn_llm_step_duration_seconds_bucket' in text
    assert "dyn_llm_step_occupancy 0.375" in text
    # live reads: new waste shows on the next scrape, no re-attach
    gp.record_waste("spec_rejected", 40)
    hedger.wasted_tokens += 3
    text = generate_latest(metrics.registry).decode()
    assert 'dyn_llm_tokens_wasted_total{cause="spec_rejected"} 40.0' in text
    assert 'dyn_llm_tokens_wasted_total{cause="hedge_loser"} 10.0' in text


async def test_http_debug_goodput_colocated_engine():
    """GET /debug/goodput on a frontend with a colocated mock engine:
    the ledger summary reflects the traffic just served."""
    drt = await DistributedRuntime.detached()
    service = None
    try:
        engine = MockEngine(MockEngineArgs(speedup_ratio=1000.0))
        config = EngineConfig.static_(engine, make_test_mdc("goodput-mock"))
        service = await run_http(drt, config, host="127.0.0.1", port=0)
        base = f"http://127.0.0.1:{service.port}"
        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"{base}/v1/completions",
                json={
                    "model": "goodput-mock",
                    "prompt": "one two three four five six",
                    "stream": True,
                    "max_tokens": 4,
                },
            ) as r:
                assert r.status == 200
                async for _ in r.content:
                    pass
            async with s.get(f"{base}/debug/goodput") as r:
                assert r.status == 200
                doc = await r.json()
        assert doc["scope"] == "frontend"
        assert doc["enabled"] is True
        summary = doc["goodput"]
        assert summary["decode_tokens"] == 4
        assert summary["steps_by_label"]["decode"]["count"] == 4
        assert set(summary["tokens_wasted"]) == set(WASTE_CAUSES)
        # the same families ride the frontend's /metrics
        async with aiohttp.ClientSession() as s:
            async with s.get(f"{base}/metrics") as r:
                text = await r.text()
        assert 'dyn_llm_device_tokens_total{phase="decode"} 4.0' in text
        await engine.close()
    finally:
        if service:
            await service.close()
        await drt.close()


# ----------------------------------------------------------- fleet e2e


async def test_fleet_debug_goodput_matches_direct_merge():
    """Three workers publish DIFFERENT goodput ledgers; the metrics
    component's fleet merge must equal a direct merge of the three —
    counts and taxonomy exactly, step percentiles within the histogram's
    documented bucket error of the pooled samples."""
    drt = await DistributedRuntime.from_settings()
    try:
        ns = drt.namespace("goodput-fleet")
        comp = ns.component("backend")
        eid = EndpointId("goodput-fleet", "backend", "generate")
        rng = random.Random(7)
        ledgers: list[GoodputLedger] = []
        all_step_ms: list[float] = []
        pubs = []
        for w in range(3):
            gp = GoodputLedger(enabled=True)
            mu = (-6.0, -5.0, -4.0)[w]  # fast / mid / slow worker
            for _ in range(300):
                dur = rng.lognormvariate(mu, 0.4)
                gp.record_step("decode", dur, lanes=2 + w, capacity=8)
                all_step_ms.append(dur * 1e3)
            gp.record_waste("spec_rejected", 10 * (w + 1))
            gp.record_waste("preempt_replay", 5)
            gp.record_compile("decode", 9.0 + w)
            ledgers.append(gp)
            fpm = ForwardPassMetrics(goodput=gp)
            pub = WorkerMetricsPublisher(comp, eid, instance_id=w)
            await pub.start(lambda m=fpm: m)
            pubs.append(pub)

        metrics = MetricsComponent(comp, eid, poll_interval=0.05, port=0)
        port = await metrics.start()
        for _ in range(100):
            last = metrics.last
            if (
                last is not None
                and last.goodput is not None
                and last.goodput.steps_total == 900
            ):
                break
            await asyncio.sleep(0.05)
        assert metrics.last.goodput.steps_total == 900

        direct = GoodputStats()
        for gp in ledgers:
            direct.merge(gp)

        async with aiohttp.ClientSession() as s:
            async with s.get(
                f"http://127.0.0.1:{port}/debug/goodput"
            ) as r:
                assert r.status == 200
                doc = await r.json()
            async with s.get(f"http://127.0.0.1:{port}/metrics") as r:
                text = await r.text()

        fleet = doc["fleet"]
        assert doc["scope"] == "fleet"
        assert len(doc["workers"]) == 3  # per-worker views ride along
        assert fleet["steps_total"] == direct.steps_total == 900
        assert fleet["tokens_wasted"] == {
            c: direct.waste_by_cause.get(c, 0) for c in WASTE_CAUSES
        }
        assert fleet["tokens_wasted"]["spec_rejected"] == 60
        assert fleet["occupancy"] == pytest.approx(direct.occupancy, abs=1e-4)
        # merged compile time is the worst worker's
        assert fleet["compile_s_by_label"]["decode"] == pytest.approx(11.0)
        # fleet percentiles agree with the pooled samples within the
        # histogram's documented relative error
        pooled = sorted(all_step_ms)
        for q in (50, 99):
            direct_ms = pooled[
                min(len(pooled) - 1, math.ceil(len(pooled) * q / 100) - 1)
            ]
            fleet_ms = fleet["steps_by_label"]["decode"][f"p{q}_ms"]
            assert abs(fleet_ms - direct_ms) / direct_ms <= (
                QUANTILE_REL_ERROR + 0.02
            ), (q, fleet_ms, direct_ms)
        # the Prometheus families on the component export the same totals
        assert "dyn_llm_steps_total 900.0" in text
        assert 'dyn_llm_tokens_wasted_total{cause="spec_rejected"} 60.0' in text
        assert 'dyn_llm_compile_seconds{label="decode"} 11.0' in text

        await metrics.close()
        for pub in pubs:
            await pub.stop()
    finally:
        await drt.close()


async def test_mock_worker_metrics_publishes_goodput():
    """The engine-free mock worker publishes the FULL goodput surface so
    dashboards and the fleet merge can run with no engine at all."""
    drt = await DistributedRuntime.from_settings()
    try:
        ns = drt.namespace("goodput-mockworker")
        comp = ns.component("backend")
        ep = comp.endpoint("generate")
        eid = EndpointId("goodput-mockworker", "backend", "generate")
        mock = MockWorkerMetrics(ep, instance_id=3)
        await mock.start()
        metrics = MetricsComponent(comp, eid, poll_interval=0.05, port=0)
        await metrics.start()
        for _ in range(100):
            last = metrics.last
            if (
                last is not None
                and last.goodput is not None
                and last.goodput.steps_total > 0
            ):
                break
            await asyncio.sleep(0.05)
        gp = metrics.last.goodput
        assert gp.steps_total > 0
        assert gp.step_hists.phases["decode"].count > 0
        assert gp.decode_tokens > 0
        assert 0.0 < gp.occupancy <= 1.0
        assert gp.waste_by_cause.get("spec_rejected", 0) > 0
        assert "prefill" in gp.compile_s_by_label
        await metrics.close()
        await mock.stop()
    finally:
        await drt.close()


# ------------------------------------------------------- overhead guard


class _CalibrationLedger:
    """What the calibration loop calls: a method of record_step's signature
    that does a fixed handful of interpreter operations (an attribute
    increment, a float add, a dict lookup and store). On a quiet host one
    call costs `QUIET_NS`; under load it slows by the factor everything
    else in the interpreter slows by, which is what it is there to read."""

    QUIET_NS = 215.0  # read on this sandbox, nothing else running

    def __init__(self) -> None:
        self.n = 0
        self.total = 0.0
        self.by: dict[str, int] = {}

    def step(self, label, elapsed, *, lanes=0, capacity=0, t_start=None):
        self.n += 1
        self.total += elapsed
        self.by[label] = self.by.get(label, 0) + lanes


def test_always_on_step_observe_overhead():
    """The ledger stays always-on in the dispatch hot path: one
    record_step must cost ~1 us (budget doubled for CI-scheduler
    jitter, matching the PR 5 trace-overhead guard's bound) on a quiet
    host. The host is seldom quiet (six test workers share it, and the
    driver's last run read over 2 us here), so each trial times a
    calibration loop of known quiet cost right before the ledger's and
    the ledger's cost is taken at the host's speed of that trial:
    record_step's time over the calibration's, times the calibration's
    quiet cost. Best of five trials: preemption and GC only ever inflate
    one of the two loops of a trial, and the least ratio is the one where
    neither was hit. The bound says what it said: 2 us at a quiet host's
    speed (record_step is 4 to 5 calibration calls there, 0.9 to 1.0 us;
    the ledger's `ssm` slot, PR 38, is recorded by a call of its own and
    adds nothing to this one)."""
    gp = GoodputLedger(enabled=True)
    cal = _CalibrationLedger()
    iters = 50_000
    per_op_ns = float("inf")
    for _ in range(5):
        gc.collect()
        t = 100.0
        t0 = time.perf_counter()
        for i in range(iters):
            cal.step("decode", 0.004, lanes=5, capacity=8, t_start=t)
            t += 0.005
        cal_ns = (time.perf_counter() - t0) / iters * 1e9
        t = 100.0
        t0 = time.perf_counter()
        for i in range(iters):
            gp.record_step(
                "decode", 0.004, lanes=5, capacity=8, t_start=t
            )
            t += 0.005
        step_ns = (time.perf_counter() - t0) / iters * 1e9
        per_op_ns = min(per_op_ns, step_ns / cal_ns * cal.QUIET_NS)
    assert gp.steps_total == 5 * iters and cal.n == 5 * iters
    assert per_op_ns < 2000, (
        f"record_step cost {per_op_ns:.0f}ns/op at a quiet host's speed"
    )


# ------------------------------------------- a stall is not a recompile


def test_launch_parts_name_where_a_dispatch_was_long():
    """The hop, the call's three phases, the call's rest and the wait to be
    resumed add up to the dispatch; the longest names it."""
    from dynamo_tpu.engine.jax_engine.model_runner import Launch
    from dynamo_tpu.telemetry.goodput import LAUNCH_PARTS, launch_parts, long_part

    launch = Launch()
    launch.upload_s, launch.enqueue_s, launch.fetch_s = 0.002, 0.003, 0.050
    parts = launch_parts(0.9, 0.8, 0.06, launch)
    assert tuple(parts) == LAUNCH_PARTS
    assert sum(parts.values()) == pytest.approx(0.9)
    assert parts["call_rest"] == pytest.approx(0.005) and parts["resume"] == pytest.approx(0.04)
    assert long_part(parts) == "hop"
    assert long_part(launch_parts(0.9, 0.001, 0.06, launch)) == "resume"
    launch.enqueue_s = 2.0
    assert long_part(launch_parts(2.2, 0.001, 2.1, launch)) == "enqueue"


def test_launch_slot_rides_the_wire_and_the_exporter():
    gp = GoodputLedger(enabled=True)
    gp.record_launch(11, 9000, 4096, why="arrival")
    gp.record_launch(21, 30000, 512, chained=True)
    gp.record_launch(0, 0, 256, dispatches=0)  # a hop that only read a result
    want = {"dispatches": 2, "upload_arrays": 32, "upload_bytes": 39000, "fetch_bytes": 4864,
            "chained": 1}
    assert gp.launch == want and gp.summary()["launch"] == want
    assert gp.chain_breaks == {"arrival": 1}
    assert gp.summary()["chain_breaks"] == {
        "arrival": 1, "prefilling": 0, "penalties": 0, "blocks": 0, "other": 0}
    back = GoodputStats.from_dict(json.loads(json.dumps(gp.to_dict())))
    assert back.launch == want
    back.merge(gp)
    assert back.launch == {k: 2 * v for k, v in want.items()}
    assert GoodputStats().summary()["launch"] == dict.fromkeys(want, 0)
    off = GoodputLedger(enabled=False)
    off.record_launch(1, 1, 1)
    off.record_stall("decode", {"fetch": 1.0})
    assert off.launch == {} and off.recompiles == {}

    class Registry:
        def collect(self):
            return goodput_families(gp)

    text = generate_latest(Registry()).decode()
    for name, value in want.items():
        assert f"dyn_llm_launch_{name}_total {float(value)}" in text


def test_sampler_slot_carries_logprob_dispatches_to_the_wire_and_the_exporter():
    """`logprob_dispatches` beside `pool_dispatches` (PR 53): a dispatch may
    take either branch, both or neither; `dispatches - logprob_dispatches`
    is how often the log-prob surface was left out."""
    gp = GoodputLedger(enabled=True)
    for pool, logprobs in ((False, False), (False, True), (True, True), (False, False)):
        gp.record_sampler(pool, logprobs)
    want = {"dispatches": 4, "pool_dispatches": 1, "logprob_dispatches": 2}
    assert gp.sampler == want and gp.summary()["sampler"] == want
    back = GoodputStats.from_dict(json.loads(json.dumps(gp.to_dict())))
    assert back.summary()["sampler"] == want
    back.merge(gp)
    assert back.summary()["sampler"] == {k: 2 * v for k, v in want.items()}
    # a frame of a version that counted the pool alone reads as no log-probs
    older = gp.to_dict()
    del older["smp"]["logprob_dispatches"]
    assert GoodputStats.from_dict(older).summary()["sampler"] == {
        **want, "logprob_dispatches": 0}

    class Registry:
        def collect(self):
            return goodput_families(gp)

    text = generate_latest(Registry()).decode()
    for name, value in want.items():
        assert f"dyn_llm_sampler_{name}_total {float(value)}" in text


@pytest.mark.parametrize("where, label, cause", [
    ("fetch", "prefill_packed", "stall"), ("enqueue", "decode_multi@H4B4", "shape_miss"),
])
async def test_a_long_warm_dispatch_is_a_recompile_only_in_its_jitted_call(
    monkeypatch, caplog, where, label, cause
):
    """A warm dispatch a hundred times its usual length: with the time in
    the runner's fetch (the next one: a packed prefill's) it is counted and
    logged as a stall, with the durations and no advice about shape buckets;
    with the time in the jitted call (`decode_multi`'s) it is the recompile
    it always was."""
    import jax

    from tests.test_jax_engine import collect
    from tests.test_layer_bodies import make_engine, request

    engine = make_engine()
    runner = engine.runner
    greedy = SamplingOptions(greedy=True)
    try:
        await collect(engine, request([5, 6, 7, 8, 9], 24, greedy))  # warm
        assert engine.stats.goodput.recompiles == {}
        # the EMAs still carry the first dispatches' compiles: say what
        # long-served labels read
        assert label in engine._dispatch_ema
        engine._dispatch_ema.update(dict.fromkeys(engine._dispatch_ema, 0.004))
        slept = []

        def once(real):
            def slow(*a, **kw):
                if not slept:
                    slept.append(time.sleep(0.4))
                return real(*a, **kw)
            return slow

        if where == "fetch":
            monkeypatch.setattr(jax, "device_get", once(jax.device_get))
        else:
            monkeypatch.setattr(runner, "_decode_multi_fn", once(runner._decode_multi_fn))
        with caplog.at_level(logging.WARNING, logger="dynamo_tpu.telemetry.goodput"):
            await collect(engine, request([9, 8, 7, 6, 5], 24, greedy))
    finally:
        await engine.close()
    assert slept
    assert engine.stats.goodput.recompiles == {f"{label}|{cause}": 1}
    said = " ".join(r.getMessage() for r in caplog.records)
    if cause == "stall":
        assert f"stalled dispatch of {label}" in said and "long in its fetch" in said
        for part in ("hop", "upload", "enqueue", "fetch"):
            assert f"{part} 0." in said
        assert "shape buckets" not in said and "recompile" not in said
    else:
        assert f"unexpected recompile of {label} (shape_miss)" in said
