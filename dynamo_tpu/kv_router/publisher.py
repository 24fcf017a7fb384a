"""Worker-side publishers: KV-cache events and load metrics.

Role-equivalent of lib/llm/src/kv_router/publisher.rs (KvEventPublisher :99,
WorkerMetricsPublisher :481) and metrics_aggregator.rs. The reference bridges
engine ZMQ feeds into NATS; we own the engine, so the publisher hooks the
JaxEngine's stored/removed callbacks directly (no shim process).

Metrics ride a lease-bound fabric kv key (`stats/...`) instead of NATS $SRV
request-reply: same pull-based scrape pattern, and worker death auto-expires
the stats entry with the lease.
"""

from __future__ import annotations

import asyncio
import contextlib
import itertools
from typing import Optional

import msgpack

from dynamo_tpu.kv_router.protocols import (
    ForwardPassMetrics,
    KvCacheEvent,
    KvCacheStoredBlock,
    KvTransferStats,
    RouterEvent,
    SpecDecodeStats,
)
from dynamo_tpu.telemetry.goodput import GoodputStats
from dynamo_tpu.telemetry.histogram import PhaseHistograms
from dynamo_tpu.runtime.component import Component
from dynamo_tpu.runtime.logging import get_logger
from dynamo_tpu.runtime.protocols import EndpointId

logger = get_logger("dynamo_tpu.kv_router.publisher")

KV_EVENT_SUBJECT = "kv_events"
STATS_ROOT = "stats/"


def stats_key(endpoint: EndpointId, instance_id: int) -> str:
    return (
        f"{STATS_ROOT}{endpoint.namespace}/{endpoint.component}/"
        f"{endpoint.name}:{instance_id:x}"
    )


class KvEventPublisher:
    """Forwards engine block store/remove callbacks as RouterEvents on the
    component's `kv_events` subject."""

    def __init__(self, component: Component, worker_id: int) -> None:
        self.component = component
        self.worker_id = worker_id
        self._event_id = itertools.count()
        self._tasks: set[asyncio.Task] = set()

    # These two match the JaxEngine hook signatures
    # (engine/jax_engine/engine.py on_blocks_stored/on_blocks_removed).

    def on_blocks_stored(self, blocks: list[dict]) -> None:
        if not blocks:
            return
        # Split into contiguous chain runs: each block carries its own
        # parent_hash, and the batch may skip already-cached blocks
        # (e.g. mocker re-storing around a warm middle block).
        run: list[dict] = []
        for b in blocks:
            if run and b.get("parent_hash") != run[-1]["block_hash"]:
                self._emit_run(run)
                run = []
            run.append(b)
        self._emit_run(run)

    def _emit_run(self, run: list[dict]) -> None:
        if not run:
            return
        event = KvCacheEvent.stored_event(
            next(self._event_id),
            run[0].get("parent_hash") or None,
            [KvCacheStoredBlock(b["block_hash"]) for b in run],
        )
        self._publish(event)

    def on_blocks_removed(self, block_hashes: list[int]) -> None:
        if not block_hashes:
            return
        self._publish(
            KvCacheEvent.removed_event(next(self._event_id), block_hashes)
        )

    def publish_cleared(self) -> None:
        self._publish(KvCacheEvent.cleared_event(next(self._event_id)))

    def _publish(self, event: KvCacheEvent) -> None:
        payload = RouterEvent(self.worker_id, event).to_dict()

        async def _send() -> None:
            with contextlib.suppress(Exception):
                await self.component.namespace.publish_event(
                    KV_EVENT_SUBJECT, payload
                )

        try:
            task = asyncio.get_running_loop().create_task(_send())
        except RuntimeError:
            return  # no loop: engine driven synchronously in tests
        self._tasks.add(task)
        task.add_done_callback(self._tasks.discard)

    async def drain(self) -> None:
        if self._tasks:
            await asyncio.gather(*self._tasks, return_exceptions=True)


class WorkerMetricsPublisher:
    """Periodically snapshots engine stats into the fabric stats key."""

    def __init__(
        self,
        component: Component,
        endpoint: EndpointId,
        instance_id: int,
        interval_s: float = 1.0,
        stamp: Optional[dict] = None,  # fencing (instance_id, epoch) stamp
    ) -> None:
        self.component = component
        self.endpoint = endpoint
        self.instance_id = instance_id
        self.interval_s = interval_s
        self.stamp = stamp
        self._task: Optional[asyncio.Task] = None
        self._latest: Optional[ForwardPassMetrics] = None

    def publish(self, metrics: ForwardPassMetrics) -> None:
        """Record the latest snapshot (watch-channel semantics: last wins)."""
        self._latest = metrics

    async def start(self, metrics_fn=None) -> None:
        """metrics_fn: optional zero-arg callable polled each interval."""
        if self._task is not None:
            return
        drt = self.component.drt
        key = stats_key(self.endpoint, self.instance_id)

        async def loop() -> None:
            while True:
                m = metrics_fn() if metrics_fn is not None else self._latest
                if m is not None:
                    with contextlib.suppress(Exception):
                        d = m.to_dict()
                        if self.stamp is not None:
                            # epoch stamp: aggregators drop publishes from
                            # a fenced incarnation (the key is lease-bound,
                            # but a zombie may republish before noticing)
                            d["stamp"] = self.stamp
                        await drt.fabric.kv_put(
                            key,
                            msgpack.packb(d, use_bin_type=True),
                            lease_id=drt.primary_lease,
                        )
                await asyncio.sleep(self.interval_s)

        self._task = asyncio.create_task(loop())

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await self._task
            self._task = None


class KvMetricsAggregator:
    """Frontend/metrics-side scrape of all workers' ForwardPassMetrics
    (reference metrics_aggregator.rs:210 + scoring.rs ProcessedEndpoints)."""

    def __init__(self, component: Component, endpoint: EndpointId) -> None:
        self.component = component
        self.endpoint = endpoint
        self._fences = None

    async def _fence_registry(self):
        if self._fences is None:
            drt = getattr(self.component, "drt", None)
            fences_fn = getattr(drt, "fences", None)
            if fences_fn is not None:
                try:
                    self._fences = await fences_fn()
                except Exception:  # noqa: BLE001 — fencing is best-effort
                    pass
        return self._fences

    async def collect(self) -> dict[int, ForwardPassMetrics]:
        prefix = (
            f"{STATS_ROOT}{self.endpoint.namespace}/"
            f"{self.endpoint.component}/{self.endpoint.name}:"
        )
        raw = await self.component.drt.fabric.kv_get_prefix(prefix)
        fences = await self._fence_registry()
        out: dict[int, ForwardPassMetrics] = {}
        for key, value in raw.items():
            try:
                instance_id = int(key.rsplit(":", 1)[1], 16)
                d = msgpack.unpackb(value, raw=False)
                if fences is not None and fences.check_stamp(
                    d.get("stamp"), "metrics"
                ):
                    # load metrics published by a fenced incarnation:
                    # scoring a zombie's slots would route work at it
                    continue
                out[instance_id] = ForwardPassMetrics.from_dict(d)
            except Exception:
                logger.exception("bad stats entry at %s", key)
        return out

    async def aggregate(
        self, per_worker: Optional[dict[int, ForwardPassMetrics]] = None
    ) -> ForwardPassMetrics:
        """Sum across workers (gauges averaged). Pass an already-collected
        snapshot to avoid a second fabric scrape (and to keep derived
        gauges consistent with it)."""
        if per_worker is None:
            per_worker = await self.collect()
        agg = ForwardPassMetrics()
        # the dataclass defaults are "one healthy idle worker" sentinels;
        # an aggregate must start from true zero or it over-counts by one
        agg.kv_stats.kv_total_blocks = 0
        agg.worker_stats.request_total_slots = 0
        n = len(per_worker)
        for m in per_worker.values():
            agg.worker_stats.request_active_slots += (
                m.worker_stats.request_active_slots
            )
            agg.worker_stats.request_total_slots += (
                m.worker_stats.request_total_slots
            )
            agg.worker_stats.num_requests_waiting += (
                m.worker_stats.num_requests_waiting
            )
            agg.worker_stats.num_deadline_exceeded += (
                m.worker_stats.num_deadline_exceeded
            )
            agg.worker_stats.num_watchdog_trips += (
                m.worker_stats.num_watchdog_trips
            )
            agg.worker_stats.num_preempted_too_often += (
                m.worker_stats.num_preempted_too_often
            )
            agg.worker_stats.num_shed_brownout += (
                m.worker_stats.num_shed_brownout
            )
            # brownout rung is a gauge: the fleet's WORST rung tells the
            # operator how degraded service currently is anywhere
            agg.worker_stats.brownout_level = max(
                agg.worker_stats.brownout_level,
                m.worker_stats.brownout_level,
            )
            if m.worker_stats.preemptions_by_class:
                if agg.worker_stats.preemptions_by_class is None:
                    agg.worker_stats.preemptions_by_class = {}
                for cls, v in m.worker_stats.preemptions_by_class.items():
                    agg.worker_stats.preemptions_by_class[cls] = (
                        agg.worker_stats.preemptions_by_class.get(cls, 0) + v
                    )
            # integrity plane: per-path/plane dict counters merge by key
            # addition, quarantine count is a fleet sum
            agg.worker_stats.num_blocks_quarantined += (
                m.worker_stats.num_blocks_quarantined
            )
            if m.worker_stats.integrity_failures_by_path:
                if agg.worker_stats.integrity_failures_by_path is None:
                    agg.worker_stats.integrity_failures_by_path = {}
                for p, v in m.worker_stats.integrity_failures_by_path.items():
                    agg.worker_stats.integrity_failures_by_path[p] = (
                        agg.worker_stats.integrity_failures_by_path.get(p, 0)
                        + v
                    )
            if m.worker_stats.fenced_rejects_by_plane:
                if agg.worker_stats.fenced_rejects_by_plane is None:
                    agg.worker_stats.fenced_rejects_by_plane = {}
                for p, v in m.worker_stats.fenced_rejects_by_plane.items():
                    agg.worker_stats.fenced_rejects_by_plane[p] = (
                        agg.worker_stats.fenced_rejects_by_plane.get(p, 0)
                        + v
                    )
            # fleet prefix cache: realized peer-pull outcomes merge by
            # key addition (same contract as the per-class preemptions)
            if m.worker_stats.kv_pulled_blocks_by_outcome:
                if agg.worker_stats.kv_pulled_blocks_by_outcome is None:
                    agg.worker_stats.kv_pulled_blocks_by_outcome = {}
                d = agg.worker_stats.kv_pulled_blocks_by_outcome
                for o, v in (
                    m.worker_stats.kv_pulled_blocks_by_outcome.items()
                ):
                    d[o] = d.get(o, 0) + v
            agg.kv_stats.kv_active_blocks += m.kv_stats.kv_active_blocks
            agg.kv_stats.kv_total_blocks += m.kv_stats.kv_total_blocks
            agg.kv_stats.gpu_cache_usage_perc += m.kv_stats.gpu_cache_usage_perc
            agg.kv_stats.gpu_prefix_cache_hit_rate += (
                m.kv_stats.gpu_prefix_cache_hit_rate
            )
            if m.spec_decode_stats is not None:
                if agg.spec_decode_stats is None:
                    agg.spec_decode_stats = SpecDecodeStats()
                agg.spec_decode_stats.merge(m.spec_decode_stats)
            if m.kv_transfer_stats is not None:
                if agg.kv_transfer_stats is None:
                    agg.kv_transfer_stats = KvTransferStats()
                agg.kv_transfer_stats.merge(m.kv_transfer_stats)
            if m.phase_histograms is not None:
                # bucket addition over the shared fixed-log grid: the
                # merged distribution is exact, so fleet percentiles are
                # true percentiles (unlike averaging per-worker p95s)
                if agg.phase_histograms is None:
                    agg.phase_histograms = PhaseHistograms()
                agg.phase_histograms.merge(m.phase_histograms)
            if m.goodput is not None:
                # goodput ledger: same contract — counters/buckets add,
                # compile times take the max
                if agg.goodput is None:
                    agg.goodput = GoodputStats()
                agg.goodput.merge(m.goodput)
        if n:
            agg.kv_stats.gpu_cache_usage_perc /= n
            agg.kv_stats.gpu_prefix_cache_hit_rate /= n
        return agg
