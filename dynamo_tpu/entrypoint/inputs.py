"""Input modes: http server, interactive text, jsonl batch, dyn:// worker.

Role-equivalent of lib/llm/src/entrypoint/input/{http,text,batch,endpoint,
common}.rs. `EngineConfig.dynamic()` serves whatever workers register via
discovery; `EngineConfig.static_(engine, mdc)` wires a local engine.
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import os
import sys
import time
from dataclasses import dataclass, field
from typing import Any, AsyncIterator, Optional

from dynamo_tpu.discovery import ModelWatcher, register_llm
from dynamo_tpu.engine import AsyncEngine
from dynamo_tpu.http.service import EngineFn, HttpService, ModelExecution, ModelManager
from dynamo_tpu.model_card import ModelDeploymentCard
from dynamo_tpu.pipeline.context import Context
from dynamo_tpu.pipeline.router import RouterMode
from dynamo_tpu.protocols.common import LLMEngineOutput, PreprocessedRequest
from dynamo_tpu.protocols.openai import ChatCompletionRequest, ChatMessage
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.logging import get_logger
from dynamo_tpu.runtime.protocols import EndpointId
from dynamo_tpu.telemetry import provenance as dprov
from dynamo_tpu.telemetry import trace as dtrace

logger = get_logger("dynamo_tpu.entrypoint")


def make_engine_handler(
    engine: Any,
    proc_label: Optional[str] = None,
    namespace: Any = None,
    stamp: Optional[dict] = None,
):
    """Worker-side request handler hosting an engine on a dyn:// endpoint.

    Every yielded frame carries the worker's epoch-fencing `stamp`
    (`(instance_id, epoch)`, runtime/fencing.py): the frontend's
    RemoteEngine rejects frames whose epoch the cluster has fenced, so a
    partitioned zombie's tokens never reach a client stream.

    With tracing enabled, the serving scope runs under a `worker_generate`
    span on the worker's own process track, and the request's completed
    spans (this worker's plus any it ingested from a prefill worker) are
    shipped back on the stream's FINAL frame so the frontend can assemble
    the whole cross-process trace. When the consumer tears the stream down
    before that frame (frontend stop sequences, max_tokens counted at the
    decoder, client disconnects), the export is published on the
    namespace's `trace-export` event subject instead — the metrics-plane
    fallback the frontend's ModelWatcher subscribes to."""

    async def handler(request: dict, ctx: Context) -> AsyncIterator[dict]:
        pre = PreprocessedRequest.from_dict(request)
        if not dtrace.enabled() and not dprov.enabled():
            async for out in engine.generate(pre, ctx):
                d = out.to_dict()
                if stamp is not None:
                    d["stamp"] = stamp
                yield d
            return
        label = proc_label or getattr(engine, "trace_proc", None)
        final_d: Optional[dict] = None
        shipped = False
        shipped_dec = False
        agen = engine.generate(pre, ctx)
        try:
            with dtrace.process_scope(label), dtrace.span(
                "worker_generate", ctx=ctx, attach=True, request_id=ctx.id
            ):
                async for out in agen:
                    d = out.to_dict()
                    if stamp is not None:
                        d["stamp"] = stamp
                    if out.finish_reason is not None:
                        # hold the final frame until the worker span has
                        # closed, so the shipped export includes it
                        final_d = d
                        break
                    yield d
            if final_d is not None:
                tid = dtrace.ctx_trace_id(ctx)
                if tid:
                    final_d["trace"] = dtrace.export_for_trace(tid)
                if dprov.enabled():
                    # this worker's why-ledger entries ride the same final
                    # frame so the frontend assembles one cross-process
                    # decision timeline
                    recs = dprov.export_for_request(ctx.id)
                    if recs:
                        final_d["decisions"] = recs
                yield final_d
                shipped = bool(final_d.get("trace"))
                shipped_dec = bool(final_d.get("decisions"))
        finally:
            with contextlib.suppress(Exception):
                await agen.aclose()
            if namespace is not None:
                payload: dict = {}
                if dtrace.enabled() and not shipped:
                    tid = dtrace.ctx_trace_id(ctx)
                    wire = dtrace.export_for_trace(tid) if tid else None
                    if wire:
                        payload["trace"] = wire
                if dprov.enabled() and not shipped_dec:
                    recs = dprov.export_for_request(ctx.id)
                    if recs:
                        payload["decisions"] = recs
                if payload:
                    # stream gone (or never reached its final frame):
                    # fire-and-forget the export onto the event plane
                    async def _publish(p=payload):
                        with contextlib.suppress(Exception):
                            await namespace.publish_event(
                                dtrace.EXPORT_SUBJECT, p
                            )

                    asyncio.get_running_loop().create_task(_publish())

    return handler


def _local_clear_fn(engine: Any) -> Optional[Any]:
    """Adapt a local engine's clear_kv_blocks() (one dict) to the
    ModelExecution.clear_fn contract (list of per-worker dicts)."""
    inner = getattr(engine, "clear_kv_blocks", None)
    if inner is None:
        return None

    async def clear_fn() -> list[dict]:
        return [{"instance": "local", **await inner()}]

    return clear_fn


@dataclass
class EngineConfig:
    """Either dynamic (discovered workers) or a static local engine."""

    engine: Optional[AsyncEngine] = None
    mdc: Optional[ModelDeploymentCard] = None
    router_mode: RouterMode = RouterMode.ROUND_ROBIN
    kv_router_config: Optional[Any] = None  # KvRouterConfig when mode=KV
    request_template: Optional[Any] = None  # request_template.RequestTemplate

    @classmethod
    def dynamic(
        cls,
        router_mode: RouterMode = RouterMode.ROUND_ROBIN,
        kv_router_config: Optional[Any] = None,
    ) -> "EngineConfig":
        return cls(router_mode=router_mode, kv_router_config=kv_router_config)

    @classmethod
    def static_(cls, engine: AsyncEngine, mdc: ModelDeploymentCard) -> "EngineConfig":
        return cls(engine=engine, mdc=mdc)

    @property
    def is_static(self) -> bool:
        return self.engine is not None

    def local_engine_fn(self) -> EngineFn:
        assert self.engine is not None
        return self.engine.generate


async def run_input(
    drt: DistributedRuntime,
    in_opt: str,
    config: EngineConfig,
    http_port: int = 8080,
    http_host: str = "0.0.0.0",
) -> None:
    """Dispatch on the input flavor (reference input.rs:101-134)."""
    if in_opt == "http":
        await run_http(drt, config, host=http_host, port=http_port)
    elif in_opt in ("text", "stdin"):
        await run_text(drt, config)
    elif in_opt.startswith("batch:"):
        await run_batch(drt, config, in_opt[len("batch:") :])
    elif in_opt.startswith("dyn://") or "." in in_opt:
        await run_endpoint(drt, config, in_opt)
    else:
        raise ValueError(f"unknown input {in_opt!r}")


# ------------------------------------------------------------------ http


async def run_http(
    drt: DistributedRuntime,
    config: EngineConfig,
    host: str = "0.0.0.0",
    port: int = 8080,
) -> HttpService:
    manager = ModelManager()
    service = HttpService(
        manager, host=host, port=port, template=config.request_template
    )
    if config.is_static:
        assert config.mdc is not None
        if getattr(config.engine, "supports_images", False):
            config.mdc.extra["supports_images"] = True
        manager.add_model(
            config.mdc.name,
            ModelExecution(
                config.mdc,
                config.local_engine_fn(),
                embed_fn=getattr(config.engine, "embed", None),
                clear_fn=_local_clear_fn(config.engine),
            ),
        )
        # colocated engine: expose spec-decode counters on the frontend
        # /metrics (only when spec decoding is actually configured)
        stats = getattr(config.engine, "stats", None)
        if stats is not None and getattr(stats, "num_spec_tokens", 0):
            service.metrics.attach_spec_stats(stats)
        # KV data-plane counters ride the same lazy-gauge path (the
        # colocated engine may act as decode OR prefill worker)
        if stats is not None and hasattr(stats, "kv_wire_bytes_rx"):
            service.metrics.attach_kv_transfer_stats(stats)
        # QoS counters (per-class preemptions, storm guard, brownout
        # sheds) for the colocated engine — both JaxEngine (stats object)
        # and MockEngine (stats() dict) carry the keys
        if stats is not None:
            service.metrics.attach_engine_qos(stats)
        # goodput ledger (ISSUE 14): step histograms, occupancy, waste
        # taxonomy, recompile forensics — both engines carry `goodput`
        if stats is not None:
            service.metrics.attach_goodput(stats)
        # admission watermark for the colocated engine follows its slot
        # count (dynamic mode gets this from the discovery capacity poller)
        if stats is not None:
            def _local_slots() -> Optional[int]:
                s = stats() if callable(stats) else stats
                d = s if isinstance(s, dict) else getattr(s, "__dict__", {})
                return d.get("total_slots") or None

            service.admission.set_capacity_fn(config.mdc.name, _local_slots)
        # colocated engine rides the frontend's brownout ladder too: the
        # engine-side rungs (spec pause, prefill-chunk cap) apply in the
        # same process — chain onto the service's admission hook
        if hasattr(config.engine, "apply_brownout"):
            local_engine = config.engine
            base_change = service.brownout.on_change

            def _chained_change(old: int, new: int, rung: str) -> None:
                if base_change is not None:
                    base_change(old, new, rung)
                local_engine.apply_brownout(new)

            service.brownout.on_change = _chained_change
    else:
        watcher = ModelWatcher(
            drt, manager, config.router_mode, config.kv_router_config,
            metrics=service.metrics, admission=service.admission,
        )
        await watcher.start()
    # SLO plane: state transitions (ok -> burning -> breached) publish a
    # `slo-status` event on the runtime namespace — the hook the planner's
    # SLA mode consumes (telemetry/slo.py)
    from dynamo_tpu.telemetry import slo as dslo

    ns = drt.namespace(drt.config.namespace)

    def _publish_slo(payload: dict) -> None:
        async def _send() -> None:
            with contextlib.suppress(Exception):
                await ns.publish_event(dslo.SLO_STATUS_SUBJECT, payload)

        with contextlib.suppress(RuntimeError):
            asyncio.get_running_loop().create_task(_send())

    service.slo_publisher = _publish_slo

    # Brownout plane (ISSUE 7): ladder transitions publish on
    # `brownout-status`, and fleet `slo-status` events (metrics component,
    # other frontends) feed this frontend's ladder so admission sheds
    # bulk/standard even when the breach was observed elsewhere.
    from dynamo_tpu.telemetry import brownout as dbrownout

    def _publish_brownout(payload: dict) -> None:
        async def _send() -> None:
            with contextlib.suppress(Exception):
                await ns.publish_event(dbrownout.BROWNOUT_SUBJECT, payload)

        with contextlib.suppress(RuntimeError):
            asyncio.get_running_loop().create_task(_send())

    service.brownout_publisher = _publish_brownout
    # control-plane health row: dyn_fabric_connected / dyn_llm_degraded_*
    # straight off this process's fabric client (degraded-mode data plane)
    service.metrics.attach_control_plane(drt.fabric.status)
    # closed-loop fleet row (ISSUE 11): if a planner publishes status on
    # this fabric, render dyn_planner_*/dyn_supervisor_* here too — the
    # frontend is the registry operators already scrape
    from dynamo_tpu.planner.samplers import PlannerStatusCache

    planner_cache = PlannerStatusCache(drt.fabric)
    await planner_cache.start()
    service.metrics.attach_planner(lambda: planner_cache.status)
    service.add_background_task(planner_cache._task)
    await service.start()

    async def _slo_event_loop() -> None:
        import msgpack

        with contextlib.suppress(asyncio.CancelledError, Exception):
            sub = await ns.subscribe_event(dslo.SLO_STATUS_SUBJECT)
            async for _subject, payload in sub:
                try:
                    data = msgpack.unpackb(payload, raw=False)
                except Exception:  # noqa: BLE001 — malformed event
                    continue
                service.note_remote_slo(data.get("new"))

    service.add_background_task(
        asyncio.get_running_loop().create_task(_slo_event_loop())
    )
    # graceful drain on SIGTERM (sdk/runner -> drt.drain): stop admitting,
    # let in-flight streams finish bounded by DYN_DRAIN_TIMEOUT_S, close
    drain_timeout = float(os.environ.get("DYN_DRAIN_TIMEOUT_S", "10"))
    drt.on_drain(lambda: service.drain(drain_timeout))
    return service


async def serve_http_forever(
    drt: DistributedRuntime, config: EngineConfig, host: str, port: int
) -> None:
    await run_http(drt, config, host, port)
    await drt.token.cancelled()


# ------------------------------------------------------------------ text


async def run_text(
    drt: DistributedRuntime, config: EngineConfig, prompt_once: Optional[str] = None
) -> None:
    """Interactive chat REPL on stdin/stdout (reference input/text.rs)."""
    execution, model_name = await _resolve_execution(drt, config)
    messages: list[ChatMessage] = []
    loop = asyncio.get_running_loop()

    async def one_turn(user_text: str) -> None:
        messages.append(ChatMessage(role="user", content=user_text))
        req = ChatCompletionRequest(
            model=model_name, messages=messages, stream=True
        )
        ctx = Context()
        reply_parts: list[str] = []
        async for item in execution.chat_stream(req, ctx):
            if item.is_error():
                print(f"\n[error] {item.error_message()}", flush=True)
                return
            if item.data:
                for choice in item.data.get("choices", []):
                    delta = choice.get("delta", {}).get("content")
                    if delta:
                        reply_parts.append(delta)
                        print(delta, end="", flush=True)
        print()
        messages.append(ChatMessage(role="assistant", content="".join(reply_parts)))

    if prompt_once is not None:
        await one_turn(prompt_once)
        return
    print(f"chatting with {model_name} — ctrl-d to exit", flush=True)
    while True:
        line = await loop.run_in_executor(None, sys.stdin.readline)
        if not line:
            return
        line = line.strip()
        if line:
            await one_turn(line)


# ----------------------------------------------------------------- batch


async def run_batch(
    drt: DistributedRuntime,
    config: EngineConfig,
    path: str,
    output_path: Optional[str] = None,
    concurrency: int = 8,
) -> dict[str, Any]:
    """JSONL batch eval with TTFT/ITL stats (reference input/batch.rs)."""
    execution, model_name = await _resolve_execution(drt, config)
    with open(path) as f:
        requests = [json.loads(line) for line in f if line.strip()]
    sem = asyncio.Semaphore(concurrency)
    results: list[dict[str, Any]] = [None] * len(requests)  # type: ignore[list-item]

    async def run_one(i: int, spec: dict[str, Any]) -> None:
        async with sem:
            prompt = spec.get("text") or spec.get("prompt") or ""
            req = ChatCompletionRequest(
                model=model_name,
                messages=[ChatMessage(role="user", content=prompt)],
                stream=True,
                max_tokens=spec.get("max_tokens"),
            )
            start = time.monotonic()
            first: Optional[float] = None
            last = start
            parts: list[str] = []
            itls: list[float] = []
            async for item in execution.chat_stream(req, Context()):
                if item.data:
                    for choice in item.data.get("choices", []):
                        delta = choice.get("delta", {}).get("content")
                        if delta:
                            now = time.monotonic()
                            if first is None:
                                first = now
                            else:
                                itls.append(now - last)
                            last = now
                            parts.append(delta)
            results[i] = {
                "text": "".join(parts),
                "ttft_ms": (first - start) * 1e3 if first else None,
                "itl_ms_mean": (sum(itls) / len(itls) * 1e3) if itls else None,
                "elapsed_ms": (time.monotonic() - start) * 1e3,
            }

    await asyncio.gather(*(run_one(i, s) for i, s in enumerate(requests)))
    ttfts = [r["ttft_ms"] for r in results if r and r["ttft_ms"] is not None]
    summary = {
        "num_requests": len(requests),
        "ttft_ms_mean": sum(ttfts) / len(ttfts) if ttfts else None,
        "results": results,
    }
    out_path = output_path or (path + ".out.jsonl")
    with open(out_path, "w") as f:
        for r in results:
            f.write(json.dumps(r) + "\n")
    logger.info(
        "batch done: %d requests, mean TTFT %.1f ms",
        len(requests),
        summary["ttft_ms_mean"] or -1,
    )
    return summary


# -------------------------------------------------------------- endpoint


async def run_endpoint(
    drt: DistributedRuntime, config: EngineConfig, endpoint_str: str
) -> None:
    """Host a static engine as a dyn:// worker and register its model
    (reference input/endpoint.rs:26-96 + bindings register_llm)."""
    if not config.is_static:
        raise ValueError("in=dyn:// requires a static engine (the worker owns it)")
    assert config.mdc is not None and config.engine is not None
    eid = EndpointId.parse(endpoint_str, drt.config.namespace)
    endpoint = (
        drt.namespace(eid.namespace).component(eid.component).endpoint(eid.name)
    )
    engine = config.engine

    # worker identity on trace timelines: distinct tracks per instance so
    # an assembled cross-process trace shows which worker served which hop
    worker_label = f"{eid.component}:{drt.primary_lease & 0xFFFFFF:x}"
    with contextlib.suppress(Exception):
        engine.trace_proc = worker_label
    # epoch-fencing stamp: (instance_id, epoch) rides every reply frame
    # so frontends can reject a fenced incarnation's tokens
    from dynamo_tpu.runtime.fencing import make_stamp

    stamp = make_stamp(drt.primary_lease, drt.fencing_epoch)
    handler = make_engine_handler(
        engine, worker_label, namespace=endpoint.component.namespace,
        stamp=stamp,
    )

    if getattr(engine, "supports_images", False):
        config.mdc.extra["supports_images"] = True
    service = await endpoint.serve_endpoint(handler)
    await register_llm(drt, endpoint, config.mdc)

    # reconcile-on-heal: when the fabric comes back from a blackout (or a
    # promoted standby's snapshot missed our in-flight registration), re-
    # register the instance + model ENTRY idempotently under the still-
    # valid lease. If the lease died during the outage the puts fail and
    # the keepalive loop self-fences — the conservative rule.
    async def _reconcile_registration() -> None:
        with contextlib.suppress(Exception):
            await drt.fabric.kv_put(
                endpoint.id.instance_key(service.instance_id),
                service.instance.to_bytes(),
                lease_id=service.instance_id,
            )
            await register_llm(drt, endpoint, config.mdc)
            logger.info(
                "reconciled %s registration after fabric heal", eid
            )

    drt.on_reconnect(_reconcile_registration)

    # self-fence: the moment a lease keepalive reports the lease gone
    # (the cluster declared us dead — possibly seconds ago, during a
    # partition), the engine fails every lane with a structured
    # `worker_fenced` error BETWEEN dispatches and the worker leaves
    # discovery — closing the up-to-TTL window where a zombie would
    # double-serve alongside its migrated replacement.
    if hasattr(engine, "fence"):
        fence_loop = asyncio.get_running_loop()

        def _on_fence(reason: str) -> None:
            engine.fence(reason)
            fence_loop.create_task(service.stop(drain=False))

        drt.on_fence(_on_fence)

    # stuck-horizon watchdog: a tripped engine pulls this worker out of
    # discovery immediately (routers stop sending; leases would take a
    # full TTL) and stops serving — the supervisor recycles the process
    if hasattr(engine, "on_watchdog_trip"):
        loop = asyncio.get_running_loop()

        def _on_trip() -> None:
            logger.error(
                "watchdog tripped: deregistering %s from discovery", eid
            )
            loop.create_task(service.stop(drain=False))

        engine.on_watchdog_trip = _on_trip

    # graceful drain on SIGTERM (sdk/runner -> drt.drain): deregister from
    # discovery and finish in-flight requests before the process exits
    drt.on_drain(lambda: service.stop(drain=True))

    # warm restart: AFTER the drain finishes (in-flight work done, its
    # completion offloads in the tiers), checkpoint the host/disk tiers +
    # prefix index to DYN_WARM_RESTART_DIR so the next incarnation boots
    # with a hot prefix cache instead of cold HBM
    if os.environ.get("DYN_WARM_RESTART_DIR") and hasattr(
        engine, "checkpoint_tiers"
    ):
        async def _warm_checkpoint() -> None:
            await asyncio.get_running_loop().run_in_executor(
                None, engine.checkpoint_tiers
            )

        drt.on_drain(_warm_checkpoint)

    # KV-routing feeds: publish engine cache events + load metrics so a
    # KV-mode frontend can prefix-route to this worker (kv_router/publisher).
    from dynamo_tpu.kv_router.protocols import (
        ForwardPassMetrics,
        KvStats,
        KvTransferStats,
        SpecDecodeStats,
        WorkerStats,
    )
    from dynamo_tpu.kv_router.publisher import (
        KvEventPublisher,
        WorkerMetricsPublisher,
    )

    kv_pub = KvEventPublisher(endpoint.component, service.instance_id)
    if hasattr(engine, "on_blocks_stored"):
        engine.on_blocks_stored = kv_pub.on_blocks_stored
        engine.on_blocks_removed = kv_pub.on_blocks_removed
    if hasattr(engine, "on_cache_cleared"):
        engine.on_cache_cleared = kv_pub.publish_cleared
    # warm restart: blocks restored from the checkpoint at boot are
    # invisible to routers until re-advertised — republish the restored
    # prefix chains now that the event publisher is wired
    bm = getattr(engine, "block_manager", None)
    if bm is not None and getattr(
        getattr(bm, "stats", None), "warm_restored", 0
    ):
        adverts = bm.advert_blocks()
        if adverts:
            kv_pub.on_blocks_stored(adverts)
            logger.info(
                "republished %d warm-restored block advert(s)", len(adverts)
            )

    # admin control plane: the frontend's POST /clear_kv_blocks fans out to
    # this per-worker endpoint (ref http/service/clear_kv_blocks.rs:23)
    clear_service = None
    if hasattr(engine, "clear_kv_blocks"):

        async def clear_handler(request: dict, ctx: Context):
            yield await engine.clear_kv_blocks()

        clear_service = await endpoint.component.endpoint(
            "clear_kv_blocks"
        ).serve_endpoint(clear_handler)

    metrics_pub = WorkerMetricsPublisher(
        endpoint.component, endpoint.id, service.instance_id, stamp=stamp
    )
    stats_fn = getattr(engine, "stats", None)

    def snapshot() -> ForwardPassMetrics:
        s = stats_fn() if callable(stats_fn) else stats_fn
        d = s if isinstance(s, dict) else getattr(s, "__dict__", {})
        total = d.get("total_blocks", 1) or 1
        used = d.get("used_blocks", 0)
        spec = None
        if d.get("num_spec_tokens") or d.get("num_drafts"):
            # speculative decoding live on this worker: ship the counters
            # so the metrics plane surfaces fleet acceptance rates
            spec = SpecDecodeStats(
                num_spec_tokens=d.get("num_spec_tokens") or None,
                num_drafts=d.get("num_drafts", 0),
                num_draft_tokens=d.get("num_draft_tokens", 0),
                num_accepted_tokens=d.get("num_accepted_tokens", 0),
                num_accepted_tokens_per_pos=(
                    list(d.get("accepted_per_pos") or []) or None
                ),
            )
        xfer = None
        if any(
            d.get(f)
            for f in (
                "kv_frames_tx", "kv_frames_rx",
                "kv_wire_bytes_tx", "kv_wire_bytes_rx",
                "prefill_dropped_expired",
            )
        ):
            # KV data plane live on this worker (prefill or decode role):
            # ship the transfer counters so /metrics surfaces fleet-wide
            # bytes shipped, frames in flight, and overlap fraction
            xfer = KvTransferStats(
                kv_frames_tx=d.get("kv_frames_tx", 0),
                kv_frames_rx=d.get("kv_frames_rx", 0),
                kv_wire_bytes_tx=d.get("kv_wire_bytes_tx", 0),
                kv_wire_bytes_rx=d.get("kv_wire_bytes_rx", 0),
                kv_bytes_overlapped=d.get("kv_bytes_overlapped", 0),
                kv_frames_inflight=d.get("kv_frames_inflight", 0),
                prefill_dropped_expired=d.get("prefill_dropped_expired", 0),
            )
        # always-on phase histograms (queue_wait/prefill/ttft/inter_token/
        # e2e): shipped whenever the engine recorded anything, so the
        # aggregator can merge fleet-true latency distributions
        ph = d.get("phase_histograms")
        if ph is not None and not getattr(ph, "total_count", lambda: 0)():
            ph = None
        # goodput ledger (ISSUE 14): shipped whenever the engine recorded
        # a step / waste / compile, so the aggregator can merge the fleet
        # efficiency view (step hists, occupancy, waste taxonomy)
        gp = d.get("goodput")
        if gp is not None and not getattr(gp, "total_events", lambda: 0)():
            gp = None
        # integrity plane: the process-wide counters (data-plane checksum
        # failures, quarantines, fence-stamp rejects) ride WorkerStats to
        # the aggregator and the metrics component
        from dynamo_tpu.integrity import COUNTERS as _icounters

        integ = _icounters.snapshot()
        return ForwardPassMetrics(
            worker_stats=WorkerStats(
                request_active_slots=d.get("active_slots", 0),
                request_total_slots=d.get("total_slots", 0),
                num_requests_waiting=d.get("waiting", 0),
                num_deadline_exceeded=d.get("deadline_exceeded", 0),
                num_watchdog_trips=d.get("watchdog_trips", 0),
                preemptions_by_class=(
                    dict(d.get("preemptions_by_class") or {}) or None
                ),
                num_preempted_too_often=d.get("preempted_too_often", 0),
                num_shed_brownout=d.get("shed_brownout", 0),
                brownout_level=d.get("brownout_level", 0),
                integrity_failures_by_path=(
                    integ["integrity_failures_by_path"] or None
                ),
                num_blocks_quarantined=integ["blocks_quarantined"],
                fenced_rejects_by_plane=(
                    integ["fenced_rejects_by_plane"] or None
                ),
                # fleet prefix cache: realized peer-pull outcomes (both
                # engines publish the dict under "kv_pull_outcomes")
                kv_pulled_blocks_by_outcome=(
                    dict(d.get("kv_pull_outcomes") or {}) or None
                ),
            ),
            kv_stats=KvStats(
                kv_active_blocks=used,
                kv_total_blocks=total,
                gpu_cache_usage_perc=used / total,
            ),
            spec_decode_stats=spec,
            kv_transfer_stats=xfer,
            phase_histograms=ph,
            goodput=gp,
        )

    if stats_fn is not None:
        await metrics_pub.start(snapshot)

    # SLO-driven brownout (ISSUE 7): the worker runs its own degradation
    # ladder fed by fleet `slo-status` events AND local burn rates over
    # the engine's own phase histograms; rungs apply through
    # engine.apply_brownout (spec pause, prefill-chunk cap, bulk shed).
    brownout_tasks: list[asyncio.Task] = []
    if hasattr(engine, "apply_brownout"):
        from dynamo_tpu.telemetry import brownout as dbrownout
        from dynamo_tpu.telemetry import slo as dslo
        from dynamo_tpu.telemetry.histogram import PhaseHistograms

        controller = dbrownout.BrownoutController(
            scope=worker_label,
            on_change=lambda old, new, rung: engine.apply_brownout(new),
        )
        slo_states = {"remote": "ok", "local": "ok"}

        def _feed(source: str, state: Any) -> None:
            if state in dslo._SEVERITY:
                slo_states[source] = state
            controller.observe(
                max(slo_states.values(), key=lambda s: dslo._SEVERITY[s])
            )

        loop_b = asyncio.get_running_loop()

        async def _slo_events() -> None:
            import msgpack

            with contextlib.suppress(asyncio.CancelledError, Exception):
                sub = await endpoint.component.namespace.subscribe_event(
                    dslo.SLO_STATUS_SUBJECT
                )
                async for _subject, payload in sub:
                    try:
                        data = msgpack.unpackb(payload, raw=False)
                    except Exception:  # noqa: BLE001 — malformed event
                        continue
                    _feed("remote", data.get("new"))

        brownout_tasks.append(loop_b.create_task(_slo_events()))

        slo_cfg = dslo.SloConfig.from_env(config.mdc.name)
        if slo_cfg.enabled and stats_fn is not None:
            local_slo = dslo.SloEngine(slo_cfg, model=config.mdc.name)
            tick_s = float(os.environ.get("DYN_SLO_TICK_S", "1.0"))

            async def _local_burn() -> None:
                with contextlib.suppress(asyncio.CancelledError):
                    while True:
                        await asyncio.sleep(tick_s)
                        try:
                            s = stats_fn() if callable(stats_fn) else stats_fn
                            d = (
                                s if isinstance(s, dict)
                                else getattr(s, "__dict__", {})
                            )
                            ph = d.get("phase_histograms")
                            status = local_slo.observe(
                                ph if ph is not None else PhaseHistograms()
                            )
                            _feed("local", status.get("state"))
                        except Exception:  # noqa: BLE001 — telemetry only
                            logger.exception("local SLO tick failed")

            brownout_tasks.append(loop_b.create_task(_local_burn()))

    logger.info("worker serving %s (model %s)", eid, config.mdc.name)
    try:
        await service.wait()
    finally:
        for t in brownout_tasks:
            t.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await t
        await metrics_pub.stop()
        if clear_service is not None:
            await clear_service.stop(drain=False)


# ----------------------------------------------------------------- util


async def _resolve_execution(
    drt: DistributedRuntime, config: EngineConfig
) -> tuple[ModelExecution, str]:
    if config.is_static:
        assert config.mdc is not None
        if getattr(config.engine, "supports_images", False):
            config.mdc.extra["supports_images"] = True
        embed_fn = getattr(config.engine, "embed", None)
        clear_fn = _local_clear_fn(config.engine)
        return (
            ModelExecution(
                config.mdc,
                config.local_engine_fn(),
                embed_fn=embed_fn,
                clear_fn=clear_fn,
            ),
            config.mdc.name,
        )
    # dynamic: wait for a discovered model
    manager = ModelManager()
    watcher = ModelWatcher(
        drt, manager, config.router_mode, config.kv_router_config
    )
    await watcher.start()
    for _ in range(300):
        models = manager.list_models()
        if models:
            execution = manager.get(models[0])
            assert execution is not None
            return execution, models[0]
        await asyncio.sleep(0.1)
    raise TimeoutError("no models discovered within 30s")
