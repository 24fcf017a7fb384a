"""Distributed request tracing (ISSUE 5): spans across frontend -> router ->
worker -> disagg, per-request timelines, and the debug/profiling surface.

Gold checks:

  * one request through the streaming-disagg MOCKER graph yields ONE
    assembled trace with >= 8 phase spans spanning >= 2 logical processes,
    renderable as valid Chrome trace-event JSON, with the same breakdown on
    the final SSE `usage` block;
  * a stream surviving a mid-stream worker death stays ONE trace — the
    replay's dispatch span parents under the original root and a
    `migration` event marks the failover;
  * the per-process ring buffer stays bounded under span churn;
  * disabled mode (`DYN_TRACE=0`, the default) hands out a shared no-op
    context manager — no allocation, no clock read;
  * `/debug/traces/{request_id}` serves the assembled cross-process trace;
  * `runtime/logging.init(force=True)` re-initializes (regression: explicit
    level= on repeat calls used to be silently ignored) and `with_fields`
    picks up the ambient trace identity.
"""

import asyncio
import json
import logging

import aiohttp
import pytest

from dynamo_tpu.disagg.transfer import (
    PrefillWorkerService,
    RemotePrefillClient,
)
from dynamo_tpu.engine.echo import EchoEngineCore
from dynamo_tpu.engine.mocker import (
    MockEngine,
    MockEngineArgs,
    MockPrefillEngine,
)
from dynamo_tpu.entrypoint.inputs import (
    EngineConfig,
    make_engine_handler,
    run_http,
)
from dynamo_tpu.discovery import register_llm
from dynamo_tpu.pipeline.context import Context
from dynamo_tpu.pipeline.router import RouterMode
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.router import StandaloneRouter
from dynamo_tpu.runtime import logging as dlog
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.telemetry import trace as dtrace

from tests.util import make_test_mdc

BS = 4


@pytest.fixture
def traced():
    """Tracing ON with a fresh ring; always restored to disabled."""
    dtrace.set_enabled(True)
    dtrace.reset(proc="frontend")
    yield
    dtrace.set_enabled(False)
    dtrace.reset()


def _spans(trace_id):
    return {s.span_id: s for s in dtrace.spans_for_trace(trace_id)}


# ----------------------------------------------------------------- core


def test_span_identity_parenting_and_events(traced):
    ctx = Context()
    with dtrace.root_span("http_request", ctx, request_id=ctx.id) as root:
        assert len(root.trace_id) == 32 and len(root.span_id) == 16
        with dtrace.span("route", ctx=ctx) as route:
            assert route.trace_id == root.trace_id
            assert route.parent_id == root.span_id
            route.set(worker="ab")
        dtrace.event("migration", cause="test")
    spans = dtrace.spans_for_trace(root.trace_id)
    assert {s.name for s in spans} == {"http_request", "route"}
    got_root = [s for s in spans if s.name == "http_request"][0]
    assert got_root.parent_id is None
    assert [e["name"] for e in got_root.events] == ["migration"]
    assert dtrace.trace_for_request(ctx.id) == root.trace_id
    # durations are monotonic-clock based and non-negative
    assert all(s.dur_ns >= 0 and s.end_ns is not None for s in spans)


def test_traceparent_roundtrip_and_rejects():
    tid, sid = "0af7651916cd43dd8448eb211c80319c", "b7ad6b7169203331"
    header = dtrace.format_traceparent(tid, sid)
    assert dtrace.parse_traceparent(header) == (tid, sid)
    assert dtrace.parse_traceparent("garbage") == (None, None)
    assert dtrace.parse_traceparent("00-" + "0" * 32 + "-" + sid + "-01") == (
        None,
        None,
    )


def test_disabled_mode_shared_noop_and_fast(traced):
    dtrace.set_enabled(False)
    # structural zero-allocation: every call hands back the same singleton
    from dynamo_tpu.telemetry.trace import NULL_CM, NULL_SPAN

    cm = dtrace.span("x", ctx=Context())
    assert cm is NULL_CM and dtrace.span("y") is NULL_CM
    assert dtrace.root_span("r", Context()) is NULL_CM
    assert dtrace.begin("b", ctx=Context()) is None
    with cm as sp:
        assert sp is NULL_SPAN
        sp.set(a=1)
        sp.event("e")
    assert dtrace.tracer().ring_len() == 0
    # loose wall bound: 100k disabled span opens must be ~instant
    import time as _t

    t0 = _t.monotonic()
    for _ in range(100_000):
        with dtrace.span("hot"):
            pass
    assert _t.monotonic() - t0 < 1.0


def test_phase_spans_without_trace_context_are_noops(traced):
    # phase spans never START traces: no root, no ctx affiliation -> no-op
    from dynamo_tpu.telemetry.trace import NULL_CM

    assert dtrace.span("orphan") is NULL_CM
    assert dtrace.tracer().ring_len() == 0


def test_ring_buffer_bounded_under_churn(traced):
    dtrace.reset(proc="t", ring=64)
    ctx = Context()
    with dtrace.root_span("root", ctx):
        for i in range(1000):
            with dtrace.span(f"phase{i % 7}", ctx=ctx):
                pass
    assert dtrace.tracer().ring_len() <= 64
    # the request index is bounded too
    for i in range(1500):
        dtrace.tracer().remember_request(f"r{i}", "t" * 32)
    assert len(dtrace.tracer()._requests) <= 1024


def test_ingest_dedupes_and_survives_garbage(traced):
    ctx = Context()
    with dtrace.root_span("root", ctx) as root:
        pass
    wire = dtrace.export_for_trace(root.trace_id)
    assert len(wire) == 1
    assert dtrace.ingest(wire) == 0  # same span_id: deduped
    foreign = dict(wire[0])
    foreign["span_id"] = "f" * 16
    foreign["proc"] = "worker-x"
    assert dtrace.ingest([foreign, {"bad": True}, "not-a-dict"]) == 1
    spans = dtrace.spans_for_trace(root.trace_id)
    assert len(spans) == 2
    assert any(s.remote and s.proc == "worker-x" for s in spans)
    # local-only export excludes ingested spans
    assert len(dtrace.export_for_trace(root.trace_id, include_remote=False)) == 1


def test_chrome_trace_export_shape(traced):
    ctx = Context()
    with dtrace.root_span("http_request", ctx, request_id=ctx.id):
        with dtrace.span("decode", ctx=ctx) as sp:
            sp.event("deadline_exceeded", phase="decode")
    tid = dtrace.trace_for_request(ctx.id)
    doc = dtrace.chrome_trace(tid)
    json.dumps(doc)  # serializable
    evs = doc["traceEvents"]
    assert any(e["ph"] == "M" and e["name"] == "process_name" for e in evs)
    slices = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in slices} == {"http_request", "decode"}
    assert all("ts" in e and e["dur"] > 0 for e in slices)
    assert any(e["ph"] == "i" and e["name"] == "deadline_exceeded" for e in evs)
    bd = dtrace.breakdown(tid)
    assert bd["spans"] == 2 and "decode" in bd["phases"]


# ------------------------------------------- mocker streaming-disagg e2e


def _mk_disagg_pair(fabric, ns="tele"):
    prefill = MockPrefillEngine(
        MockEngineArgs(block_size=BS, speedup_ratio=1000.0), chunk_blocks=2
    )
    prefill.trace_proc = "prefill-0"
    service = PrefillWorkerService(fabric, ns, prefill)
    client = RemotePrefillClient(fabric, ns, block_size=BS)
    decode = MockEngine(
        MockEngineArgs(block_size=BS, speedup_ratio=1000.0),
        remote_prefill_client=client,
        disagg_threshold=2 * BS,
    )
    decode.trace_proc = "decode-0"
    return prefill, service, client, decode


async def test_mocker_disagg_one_trace_eight_spans_two_procs(traced, tmp_path, monkeypatch):
    """Acceptance: a single request through the streaming-disagg mocker
    graph yields ONE trace with >= 8 phase spans across >= 2 logical
    processes, valid Chrome JSON, and the breakdown in the SSE usage."""
    monkeypatch.setenv("DYN_TRACE_DIR", str(tmp_path))
    drt = await DistributedRuntime.detached()
    http_service = None
    try:
        prefill, service, client, decode = _mk_disagg_pair(drt.fabric)
        await service.start()
        await client.start()
        config = EngineConfig.static_(decode, make_test_mdc("tele-mock"))
        http_service = await run_http(drt, config, host="127.0.0.1", port=0)
        base = f"http://127.0.0.1:{http_service.port}"
        words = "the quick brown fox jumps over lazy dog one two three four"
        async with aiohttp.ClientSession() as s:
            async with s.post(
                f"{base}/v1/completions",
                headers={
                    "x-request-id": "trace me/../weird#id",
                    "traceparent":
                        "00-0af7651916cd43dd8448eb211c80319c-"
                        "b7ad6b7169203331-01",
                },
                json={
                    "model": "tele-mock",
                    "prompt": words,
                    "stream": True,
                    "max_tokens": 6,
                    "stream_options": {"include_usage": True},
                },
            ) as r:
                assert r.status == 200
                # sanitized client request id echoes on the SSE response
                rid = r.headers["x-request-id"]
                assert rid == "trace-me-..-weird-id"
                assert (
                    r.headers["x-dyn-trace-id"]
                    == "0af7651916cd43dd8448eb211c80319c"
                )
                usage = None
                async for raw in r.content:
                    line = raw.decode().strip()
                    if line.startswith("data: ") and line != "data: [DONE]":
                        d = json.loads(line[len("data: "):])
                        if d.get("usage"):
                            usage = d["usage"]
            # breakdown rides the final SSE usage block
            assert usage is not None and "timing" in usage
            phases = usage["timing"]["phases"]
            for want in ("queue_wait", "remote_prefill", "decode",
                         "prefill_serve", "kv_land"):
                assert want in phases, (want, sorted(phases))

            # /debug/traces/{request_id}: the assembled cross-process trace
            async with s.get(f"{base}/debug/traces/{rid}") as r:
                assert r.status == 200
                doc = await r.json()
        json.dumps(doc)  # valid Chrome trace-event JSON
        # inbound traceparent honored end to end
        assert doc["otherData"]["trace_id"] == (
            "0af7651916cd43dd8448eb211c80319c"
        )
        slices = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert len(slices) >= 8, [e["name"] for e in slices]
        procs = {
            e["args"]["name"]
            for e in doc["traceEvents"]
            if e["ph"] == "M" and e["name"] == "process_name"
        }
        assert len(procs) >= 2, procs
        assert {"decode-0", "prefill-0"} <= procs
        names = {e["name"] for e in slices}
        for want in ("http_request", "queue_wait", "remote_prefill",
                     "kv_land", "decode", "prefill_serve", "prefill_chunk"):
            assert want in names, (want, sorted(names))
        # phase spans are ordered: the request flowed ingress -> prefill ->
        # decode (same-trace spans, cross-checked on the shared ring)
        tid = doc["otherData"]["trace_id"]
        by_name = {}
        for s_ in dtrace.spans_for_trace(tid):
            by_name.setdefault(s_.name, s_)
        assert (
            by_name["http_request"].start_unix_ns
            <= by_name["remote_prefill"].start_unix_ns
            <= by_name["decode"].start_unix_ns
        )
        # queue_wait closed before decode started (non-overlapping phases)
        qw = by_name["queue_wait"]
        assert qw.start_ns + qw.dur_ns <= by_name["decode"].start_ns
        # DYN_TRACE_DIR: the per-request Chrome trace landed on disk
        files = list(tmp_path.glob("trace-*.json"))
        assert files, "DYN_TRACE_DIR got no trace file"
        on_disk = json.loads(files[0].read_text())
        assert on_disk["traceEvents"]
    finally:
        if http_service is not None:
            await http_service.close()
        await drt.close()


async def test_migration_replay_is_one_trace(traced):
    """A stream surviving a mid-stream worker death is ONE trace: two
    dispatch spans under the same root, worker spans from both workers'
    tracks, and a `migration` event marking the failover."""

    class DyingEngine:
        def __init__(self, die_after=3):
            self.inner = EchoEngineCore()
            self.die_after = die_after

        async def generate(self, request, context):
            n = 0
            async for out in self.inner.generate(request, context):
                if out.finish_reason is None and n >= self.die_after:
                    raise ConnectionResetError("worker died mid-stream")
                yield out
                n += 1

    worker_a = await DistributedRuntime.detached()
    worker_b = await DistributedRuntime.detached()
    front = await DistributedRuntime.detached()
    service = None
    try:
        mdc = make_test_mdc("tele-mig")
        dying, healthy = DyingEngine(), EchoEngineCore()
        ep_a = worker_a.namespace("tm").component("worker").endpoint("generate")
        await ep_a.serve_endpoint(make_engine_handler(dying, "worker-a"))
        await register_llm(worker_a, ep_a, mdc)
        ep_b = worker_b.namespace("tm").component("worker").endpoint("generate")
        await ep_b.serve_endpoint(make_engine_handler(healthy, "worker-b"))
        await register_llm(worker_b, ep_b, mdc)
        config = EngineConfig.dynamic(RouterMode.ROUND_ROBIN)
        service = await run_http(front, config, host="127.0.0.1", port=0)
        base = f"http://127.0.0.1:{service.port}"
        words = "the quick brown fox jumps over lazy dog one two".split()
        async with aiohttp.ClientSession() as s:
            for _ in range(50):
                async with s.get(f"{base}/v1/models") as r:
                    if (await r.json())["data"]:
                        break
                await asyncio.sleep(0.1)

            async def stream_one(rid):
                async with s.post(
                    f"{base}/v1/completions",
                    headers={"x-request-id": rid},
                    json={
                        "model": "tele-mig",
                        "prompt": " ".join(words),
                        "stream": True,
                        "max_tokens": 10,
                    },
                ) as r:
                    assert r.status == 200
                    async for _ in r.content:
                        pass

            # round-robin over 2 workers: two requests guarantee one lands
            # on the dying worker and must migrate mid-stream
            await asyncio.wait_for(stream_one("mig-0"), timeout=30)
            await asyncio.wait_for(stream_one("mig-1"), timeout=30)
        migrated = None
        for rid in ("mig-0", "mig-1"):
            tid = dtrace.trace_for_request(rid)
            spans = dtrace.spans_for_trace(tid)
            dispatches = sorted(
                (s for s in spans if s.name == "dispatch"),
                key=lambda s: s.attrs.get("attempt", 0),
            )
            if len(dispatches) >= 2:
                migrated = (tid, spans, dispatches)
                break
        assert migrated is not None, "no request migrated"
        tid, spans, dispatches = migrated
        # ONE trace id across every hop, replay included
        assert all(s.trace_id == tid for s in spans)
        root = [s for s in spans if s.name == "http_request"]
        assert len(root) == 1
        # every dispatch attempt (original AND replay) parents on the root
        assert all(d.parent_id == root[0].span_id for d in dispatches)
        assert dispatches[0].attrs["attempt"] == 1
        assert dispatches[1].attrs["attempt"] == 2
        # the replay carried the already-emitted tokens
        assert dispatches[1].attrs["replayed_tokens"] >= 1
        # worker spans from two distinct process tracks in the same trace
        worker_procs = {s.proc for s in spans if s.name == "worker_generate"}
        assert {"worker-a", "worker-b"} <= worker_procs
        # migration event recorded on the root span
        events = [e["name"] for e in root[0].events]
        assert "migration" in events
    finally:
        if service is not None:
            await service.close()
        for drt in (front, worker_a, worker_b):
            await drt.close()


async def test_pipeline_closes_engine_generator_promptly(traced):
    """Regression (found driving a real multi-process deployment): when
    the frontend decoder finishes a stream (max_tokens counted at the
    decoder), the pipeline must aclose the engine generator NOW — GC-
    deferred asyncgen finalization left worker streams open and dropped
    every span still suspended inside a `with` (RemoteEngine's dispatch
    span, the worker's shipped trace)."""
    from dynamo_tpu.http.service import ModelExecution
    from dynamo_tpu.protocols.common import LLMEngineOutput
    from dynamo_tpu.protocols.openai import CompletionRequest

    closed = asyncio.Event()

    async def engine_fn(req, ctx):
        try:
            for t in req.token_ids:
                yield LLMEngineOutput(token_ids=[t])
        finally:
            closed.set()

    execution = ModelExecution(make_test_mdc("close-t"), engine_fn)
    req = CompletionRequest(
        model="close-t", prompt="one two three four five six",
        stream=True, max_tokens=2,
    )
    async for _ in execution.completion_stream(req, Context()):
        pass
    # deterministic: closed by the pipeline's finally, not by the GC
    assert closed.is_set()


# ------------------------------------------------------- debug endpoints


async def test_debug_trace_endpoint_disabled_and_missing(traced):
    drt = await DistributedRuntime.detached()
    service = None
    try:
        engine = MockEngine(MockEngineArgs(block_size=BS, speedup_ratio=1000.0))
        config = EngineConfig.static_(engine, make_test_mdc("tele-404"))
        service = await run_http(drt, config, host="127.0.0.1", port=0)
        base = f"http://127.0.0.1:{service.port}"
        async with aiohttp.ClientSession() as s:
            async with s.get(f"{base}/debug/traces/nope") as r:
                assert r.status == 404  # enabled, but no such trace
            dtrace.set_enabled(False)
            async with s.get(f"{base}/debug/traces/nope") as r:
                assert r.status == 404
                assert "disabled" in (await r.json())["error"]["message"]
    finally:
        if service is not None:
            await service.close()
        await drt.close()


async def test_debug_profile_endpoint(tmp_path):
    drt = await DistributedRuntime.detached()
    service = None
    try:
        engine = MockEngine(MockEngineArgs(block_size=BS, speedup_ratio=1000.0))
        config = EngineConfig.static_(engine, make_test_mdc("tele-prof"))
        service = await run_http(drt, config, host="127.0.0.1", port=0)
        base = f"http://127.0.0.1:{service.port}"
        async with aiohttp.ClientSession() as s:
            async with s.get(
                f"{base}/debug/profile",
                params={"seconds": "0.2", "dir": str(tmp_path)},
            ) as r:
                assert r.status == 200
                info = await r.json()
                assert info["profile_dir"].startswith(str(tmp_path))
            # a second request while the window is open conflicts
            async with s.get(
                f"{base}/debug/profile", params={"seconds": "0.2"}
            ) as r:
                assert r.status == 409
            async with s.get(
                f"{base}/debug/profile", params={"seconds": "abc"}
            ) as r:
                assert r.status == 400
        from dynamo_tpu.telemetry import profile as dprofile

        for _ in range(40):
            if not dprofile.active():
                break
            await asyncio.sleep(0.1)
        assert not dprofile.active()
        # jax.profiler wrote its artifacts under the requested dir
        assert any(tmp_path.rglob("*"))
    finally:
        if service is not None:
            await service.close()
        await drt.close()


# ----------------------------------------------- engine disabled fast path


async def test_mocker_disabled_mode_records_nothing():
    assert not dtrace.enabled()
    dtrace.reset()
    engine = MockEngine(MockEngineArgs(block_size=BS, speedup_ratio=1000.0))
    req = PreprocessedRequest(
        token_ids=list(range(2, 14)),
        sampling=SamplingOptions(greedy=True),
        stop=StopConditions(max_tokens=4, ignore_eos=True),
    )
    toks = []
    async for out in engine.generate(req, Context()):
        toks.extend(out.token_ids)
    assert toks
    assert dtrace.tracer().ring_len() == 0


def test_disabled_overhead_guard():
    """Tier-1 guard: the DYN_TRACE=0 fast path must stay near-free. Each
    disabled instrumentation call is one flag check + shared singleton —
    bound it loosely (2 µs/op vs the ~0.1 µs measured) so only a real
    regression (per-call allocation, clock read, lock) trips it."""
    from benchmarks.trace_overhead_bench import measure_noop_ns

    assert not dtrace.enabled()
    # the least of five repetitions: the suite runs beside five other
    # workers, and one descheduled loop of 50,000 calls is the machine's
    # time, not the call's (ISSUE 28: it failed once in the driver's run)
    runs = [measure_noop_ns(iters=50_000) for _ in range(5)]
    for name in runs[0]:
        per_op = min(r[name] for r in runs)
        assert per_op < 2000, f"disabled {name}() costs {per_op} ns/op"


# -------------------------------------------------- kv hit-rate satellite


def test_scheduler_hit_stats_accumulate():
    from dynamo_tpu.kv_router.indexer import OverlapScores
    from dynamo_tpu.kv_router.scheduler import KvScheduler

    sched = KvScheduler(block_size=4)
    sched.update_workers([1, 2])
    ov = OverlapScores()
    ov.scores[1] = 2  # worker 1 holds 2 of the request's 4 blocks
    res = sched.schedule(list(range(16)), ov, request_id="r1")
    assert res.required_blocks == 4
    assert sched.hit_stats["decisions"] == 1
    assert sched.hit_stats["isl_blocks"] == 4
    if res.worker_id == 1:
        assert sched.hit_stats["matched_blocks"] == 2
        assert sched.hit_rate == 0.5
    else:
        assert sched.hit_stats["matched_blocks"] == 0


def test_frontend_metrics_expose_kv_hit_rate():
    from dynamo_tpu.http.metrics import ServiceMetrics

    class FakeSched:
        hit_stats = {"decisions": 3, "isl_blocks": 10, "matched_blocks": 4,
                     "fleet_blocks": 7}
        hit_rate = 0.4
        fleet_hit_rate = 0.7
        pull_stats = {"plans": 1, "planned_blocks": 3}

    m = ServiceMetrics()
    m.attach_kv_hit_stats(FakeSched())
    m.attach_kv_hit_stats(FakeSched())  # idempotent: no duplicate series
    text = m.render().decode()
    assert "dyn_llm_kv_hit_rate 0.4" in text
    assert "dyn_llm_kv_matched_blocks_total 4.0" in text
    assert "dyn_llm_kv_fleet_hit_rate 0.7" in text
    assert 'dyn_llm_kv_pulled_blocks_total{outcome="pulled"} 0.0' in text


async def test_standalone_router_trace_and_metrics(traced):
    """The find_best hop joins the request trace (span shipped back in the
    reply) and the router exposes its own /metrics with the hit-rate
    plane."""
    drt = await DistributedRuntime.detached()
    router = None
    try:
        component = drt.namespace("tr").component("backend")
        ep = component.endpoint("generate")
        engine = MockEngine(MockEngineArgs(block_size=BS, speedup_ratio=1000.0))

        async def handler(request, context):
            req = PreprocessedRequest.from_dict(request)
            async for out in engine.generate(req, context):
                yield out.to_dict()

        await ep.serve_endpoint(handler)
        router = StandaloneRouter(
            drt, namespace="tr", component="backend", endpoint="generate",
            block_size=BS, metrics_port=0,
        )
        await router.start()
        finder = await (
            drt.namespace("tr").component("router").endpoint("find_best")
        ).client()
        await finder.wait_for_instances(2.0)

        ctx = Context()
        with dtrace.root_span("http_request", ctx, request_id=ctx.id):
            stream = await finder.direct(
                {"token_ids": list(range(2 * BS)), "request_id": ctx.id},
                finder.instance_ids()[0], ctx,
            )
            decision = None
            async for item in stream:
                decision = item.data if hasattr(item, "data") else item
        assert "worker_id" in decision
        # the router shipped its span back: fold it in and assemble
        assert decision.get("trace"), decision
        dtrace.ingest(decision["trace"])
        tid = dtrace.trace_for_request(ctx.id)
        spans = dtrace.spans_for_trace(tid)
        route = [s for s in spans if s.name == "route_decision"]
        assert route and route[0].proc == "router"
        assert route[0].attrs["overlap_blocks"] >= 0

        port = router._status_server.port
        async with aiohttp.ClientSession() as s:
            async with s.get(f"http://127.0.0.1:{port}/metrics") as r:
                text = await r.text()
        assert "dyn_llm_kv_hit_rate" in text
        assert "dyn_llm_kv_matched_blocks_total" in text
        assert "dyn_llm_router_decisions_total 1.0" in text
    finally:
        if router is not None:
            await router.close()
        await drt.close()


# --------------------------------------------------- logging satellites


def test_logging_force_reinit_regression(monkeypatch):
    # force a known baseline, then verify repeat calls without force are
    # ignored (the old silent behavior, now with a loud warning) and
    # force=True actually re-initializes
    dlog.init(level="info", force=True)
    root = logging.getLogger()
    assert root.level == logging.INFO
    dlog.init(level="trace")  # repeat without force: ignored
    assert root.level == logging.INFO
    dlog.init(level="trace", force=True)
    assert root.level == 5
    dlog.init(level="info", force=True)  # restore for other tests
    assert root.level == logging.INFO


def test_with_fields_injects_trace_identity(traced):
    records = []

    class Capture(logging.Handler):
        def emit(self, record):
            records.append(record)

    logger = logging.getLogger("dynamo_tpu.test.tele")
    logger.setLevel(logging.INFO)
    h = Capture()
    logger.addHandler(h)
    try:
        ctx = Context(id="rid-42")
        with dtrace.root_span("http_request", ctx, request_id=ctx.id):
            dlog.with_fields(logger, logging.INFO, "inside span", step=1)
        dlog.with_fields(logger, logging.INFO, "outside span", step=2)
    finally:
        logger.removeHandler(h)
    inside = records[0].fields
    assert inside["request_id"] == "rid-42"
    assert len(inside["trace_id"]) == 32 and inside["step"] == 1
    # no ambient span: only the explicit fields
    assert "trace_id" not in records[1].fields
