"""Integrity plane tests (ISSUE 8): checksummed KV data plane,
poison-block quarantine, epoch fencing, wire versioning, shared backoff.

Gold checks:

  * a flipped bit or truncated payload anywhere (disagg frame, final
    response, peer pull, host arena, disk spill page) is caught by the
    content checksum at land/promote time and NEVER decoded;
  * a block that fails verification repeatedly is quarantined: freed
    exactly once, excluded from prefix offers, and an offload/onboard
    round-trip cannot resurrect it;
  * a zombie worker (partition swallows its lease keepalives while the
    cluster expires the lease) self-fences the moment a keepalive fails,
    and its stamped frames are rejected by consumers via the fabric's
    ``fence/`` tombstones;
  * a version-skewed fabric peer fails at handshake with a structured
    mismatch error, not a framing mis-parse.
"""

import asyncio
import contextlib
import os
import time

import msgpack
import numpy as np
import pytest

from dynamo_tpu import integrity
from dynamo_tpu.block_manager.layout import LayoutConfig
from dynamo_tpu.block_manager.manager import TieredBlockManager
from dynamo_tpu.disagg.protocols import KvBlockPayload, KvStreamFrame
from dynamo_tpu.disagg.transfer import (
    PrefillWorkerService,
    RemotePrefillClient,
)
from dynamo_tpu.engine.mocker import (
    MockEngine,
    MockEngineArgs,
    MockPrefillEngine,
)
from dynamo_tpu.fabric import wire
from dynamo_tpu.fabric.client import FabricClient
from dynamo_tpu.fabric.state import FabricState
from dynamo_tpu.kv_router.protocols import ForwardPassMetrics, WorkerStats
from dynamo_tpu.kv_router.publisher import KvMetricsAggregator, stats_key
from dynamo_tpu.pipeline.annotated import Annotated
from dynamo_tpu.pipeline.context import Context
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.backoff import Backoff, full_jitter_delay
from dynamo_tpu.runtime.config import RuntimeConfig
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.fencing import (
    FENCE_ROOT,
    FenceRegistry,
    fence_key,
    make_stamp,
)
from dynamo_tpu.runtime.protocols import EndpointId
from dynamo_tpu.testing import faults

BS = 4
LAYOUT = LayoutConfig(
    num_layers=2, page_size=BS, num_kv_heads=2, head_dim=16, dtype="float32"
)


@pytest.fixture(autouse=True)
def _clean_counters():
    integrity.COUNTERS.reset()
    yield
    integrity.COUNTERS.reset()
    faults.set_injector(None)


def _blocks(n, seed=0):
    rng = np.random.default_rng(seed)
    shape = (LAYOUT.num_layers, LAYOUT.num_kv_heads, n, BS, LAYOUT.head_dim)
    return (
        rng.standard_normal(shape).astype(np.float32),
        rng.standard_normal(shape).astype(np.float32),
    )


def _req(prompt, max_tokens):
    return PreprocessedRequest(
        token_ids=list(prompt),
        sampling=SamplingOptions(),
        stop=StopConditions(max_tokens=max_tokens),
    )


# ------------------------------------------------------------ checksums


def test_checksum_deterministic_and_chunked():
    a = integrity.checksum(b"hello", b"world")
    assert a == integrity.checksum(b"hello", b"world")
    assert a == integrity.checksum_with(integrity.ALGO, b"hello", b"world")
    assert a != integrity.checksum(b"helloworlx")
    # unknown algo: verification must be skipped, not false-alarmed
    assert integrity.checksum_with("no-such-algo", b"x") is None


def test_payload_verify_catches_bitflip_and_truncation():
    k, v = _blocks(3)
    p = KvBlockPayload.encode(k, v)
    assert p.sum_algo == integrity.ALGO
    p.verify()  # clean payload passes
    kk, vv = p.decode()
    np.testing.assert_array_equal(kk, k)
    np.testing.assert_array_equal(vv, v)
    # single flipped bit in the k payload
    bad = bytearray(p.k_bytes)
    bad[len(bad) // 2] ^= 0x10
    p_bad = KvBlockPayload.from_wire({**p.to_wire(), "k": bytes(bad)})
    with pytest.raises(integrity.IntegrityError):
        p_bad.decode()
    # truncation changes the byte string -> checksum mismatch, caught
    # BEFORE any frombuffer/reshape could misfire
    p_trunc = KvBlockPayload.from_wire(
        {**p.to_wire(), "k": p.k_bytes[: len(p.k_bytes) // 2]}
    )
    with pytest.raises(integrity.IntegrityError):
        p_trunc.decode()
    # int8 codec: scales are covered too
    p8 = KvBlockPayload.encode(k, v, "int8")
    p8.verify()
    bad_scales = bytearray(p8.k_scales)
    bad_scales[0] ^= 0x01
    p8_bad = KvBlockPayload.from_wire(
        {**p8.to_wire(), "ks": bytes(bad_scales)}
    )
    with pytest.raises(integrity.IntegrityError):
        p8_bad.decode()


def test_payload_checksum_env_disable(monkeypatch):
    monkeypatch.setenv("DYN_KV_CHECKSUM", "0")
    k, v = _blocks(1)
    p = KvBlockPayload.encode(k, v)
    assert p.sum_algo == "" and p.k_sum == 0
    p.decode()  # untagged payloads are accepted unverified
    # wire form carries no integrity keys -> older receivers unaffected
    assert "alg" not in p.to_wire()


# ------------------------------------------------------ fault harness


def test_fault_spec_parses_new_actions():
    s = faults.FaultSpec.parse("corrupt_kv=bits,every=3")
    assert s.corrupt_kv == "bits" and s.every == 3
    s = faults.FaultSpec.parse("zombie_partition=1.5")
    assert s.zombie_partition_s == 1.5
    with pytest.raises(ValueError):
        faults.FaultSpec.parse("corrupt_kv=nonsense")


def test_corrupt_bytes_modes_and_cadence():
    inj = faults.FaultInjector(
        faults.FaultSpec(corrupt_kv="bits", every=2)
    )
    data = bytes(64)
    assert inj.corrupt_bytes(data) is None  # visit 1 of every=2
    out = inj.corrupt_bytes(data)  # visit 2 fires
    assert out is not None and out != data and len(out) == len(data)
    # exactly one bit differs
    diff = [a ^ b for a, b in zip(data, out)]
    assert sum(bin(d).count("1") for d in diff) == 1
    trunc = faults.FaultInjector(faults.FaultSpec(corrupt_kv="truncate"))
    out = trunc.corrupt_bytes(data)
    assert out is not None and len(out) == len(data) // 2


# ------------------------------------------------- tier integrity


def test_host_arena_corruption_fails_load_then_quarantines(tmp_path):
    events = []
    m = TieredBlockManager(
        LAYOUT, host_blocks=8,
        on_event=lambda kind, hs, tier: events.append((kind, hs, tier)),
    )
    k, v = _blocks(2)
    assert m.store_blocks([100, 101], k, v) == 2
    free_before = len(m._free_slots)
    # flip one byte in block 100's arena slot (host-RAM bit rot)
    slot = m._host[100].index
    m._k_arena[slot].reshape(-1).view(np.uint8)[7] ^= 0x04
    with pytest.raises(integrity.IntegrityError):
        m.load_blocks([100, 101])
    assert m.stats.integrity_failures == 1
    assert integrity.COUNTERS.failures.get("tier_host") == 1
    # freed exactly once: the slot returned to the free list, hash gone
    assert 100 not in m and len(m._free_slots) == free_before + 1
    assert ("removed", [100], 2) in events
    # not yet quarantined (default threshold 2): a re-store is accepted
    assert not m.is_quarantined(100)
    assert m.store_blocks([100], k[:, :, :1], v[:, :, :1]) == 1
    # second corruption of the same hash tips it into quarantine
    slot = m._host[100].index
    m._v_arena[slot].reshape(-1).view(np.uint8)[3] ^= 0x80
    with pytest.raises(integrity.IntegrityError):
        m.load_blocks([100])
    assert m.is_quarantined(100)
    assert m.stats.quarantined == 1
    assert integrity.COUNTERS.blocks_quarantined == 1
    # quarantined: never re-admitted (no resurrection through offload
    # round-trips), treated as a prefix miss, refused with a counted stat
    assert m.store_blocks([100], k[:, :, :1], v[:, :, :1]) == 0
    assert m.stats.quarantine_refused == 1
    assert m.lookup_prefix([100, 101]) == 0
    assert 101 in m  # the healthy neighbour is untouched
    # block count conservation: slots used == live host entries
    assert len(m._free_slots) == 8 - len(m._host)


def test_disk_spill_torn_page_fails_promotion(tmp_path):
    m = TieredBlockManager(LAYOUT, host_blocks=1, disk_dir=str(tmp_path))
    k, v = _blocks(2, seed=3)
    # arena holds 1: storing 2 spills the LRU block to disk
    assert m.store_blocks([200, 201], k, v) == 2
    assert 200 in m._disk
    path = m._disk[200]
    # tear the page: truncate half of it
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(size // 2)
    with pytest.raises(integrity.IntegrityError):
        m.load_blocks([200])
    assert integrity.COUNTERS.failures.get("tier_disk") == 1
    assert 200 not in m and not os.path.exists(path)
    # a clean disk page still promotes fine after the failure
    kk, vv = m.load_blocks([201])
    assert kk.shape[2] == 1


def test_corrupt_kv_fault_fires_in_tier_store(tmp_path):
    """DYN_FAULT=corrupt_kv corrupts the arena AFTER checksumming, so the
    next onboard catches it — the full injected-fault loop."""
    faults.set_injector(
        faults.FaultInjector(faults.FaultSpec(corrupt_kv="bits"))
    )
    m = TieredBlockManager(LAYOUT, host_blocks=4)
    k, v = _blocks(1, seed=4)
    assert m.store_blocks([300], k, v) == 1
    with pytest.raises(integrity.IntegrityError):
        m.load_blocks([300])
    assert m.stats.integrity_failures == 1


def test_quarantined_block_leaves_router_prefix_offers():
    """Quarantine bookkeeping end to end against the router's radix tree:
    the manager's `removed` event (emitted on quarantine) drops the block
    from every worker's prefix-reuse offers, and — because store_blocks
    refuses resurrection — no later offload round-trip re-offers it."""
    from dynamo_tpu.kv_router.indexer import RadixTree
    from dynamo_tpu.kv_router.protocols import (
        KvCacheEvent,
        KvCacheStoredBlock,
        RouterEvent,
    )

    tree = RadixTree()
    worker = 42
    events = []
    m = TieredBlockManager(
        LAYOUT, host_blocks=4,
        on_event=lambda kind, hs, tier: events.append((kind, hs)),
    )
    # the worker advertised two chained blocks to the router
    tree.apply_event(RouterEvent(worker, KvCacheEvent.stored_event(
        0, None, [KvCacheStoredBlock(1111)]
    )))
    tree.apply_event(RouterEvent(worker, KvCacheEvent.stored_event(
        1, 1111, [KvCacheStoredBlock(2222)]
    )))
    assert tree.find_matches([1111, 2222]).scores.get(worker) == 2
    # corrupt block 2222 into quarantine (threshold 2)
    k, v = _blocks(1, seed=9)
    for _ in range(2):
        assert m.store_blocks([2222], k, v) == 1
        slot = m._host[2222].index
        m._k_arena[slot].reshape(-1).view(np.uint8)[0] ^= 1
        with pytest.raises(integrity.IntegrityError):
            m.load_blocks([2222])
    assert m.is_quarantined(2222)
    # replay the manager's removal events into the router tree, exactly
    # as KvEventPublisher.on_blocks_removed ships them
    eid = 10
    for kind, hashes in events:
        if kind == "removed":
            tree.apply_event(RouterEvent(
                worker, KvCacheEvent.removed_event(eid, hashes)
            ))
            eid += 1
    # the poisoned block is no longer offered; the healthy prefix is
    assert tree.find_matches([1111, 2222]).scores.get(worker, 0) == 1
    # no resurrection: a re-store is refused, so no new Stored event can
    # ever re-offer the hash
    assert m.store_blocks([2222], k, v) == 0
    assert m.stats.quarantine_refused >= 1


# ------------------------------------------- disagg stream (mock e2e)


async def test_corrupt_disagg_frames_dropped_stream_token_identical():
    """Every streamed frame corrupted on the wire: the client drops them
    at land time, the final response (also corrupt) degrades to a
    structured error, and the mocker falls back to its local prefill —
    the token stream is IDENTICAL to a fault-free run."""
    fabric = FabricClient.in_process(FabricState())
    ns = "integ-stream"
    prompt = list(range(2, 2 + 4 * BS))  # 4 full blocks
    # fault-free reference
    ref_engine = MockEngine(MockEngineArgs(block_size=BS,
                                           speedup_ratio=1000.0))
    ref = []
    async for out in ref_engine.generate(_req(prompt, 8), Context()):
        ref.extend(out.token_ids)
    await ref_engine.close()

    prefill = MockPrefillEngine(
        MockEngineArgs(block_size=BS, speedup_ratio=1000.0), chunk_blocks=1
    )
    service = PrefillWorkerService(fabric, ns, prefill,
                                   stamp=make_stamp(7, 7))
    client = RemotePrefillClient(fabric, ns, block_size=BS, timeout=10)
    decode = MockEngine(
        MockEngineArgs(block_size=BS, speedup_ratio=1000.0),
        remote_prefill_client=client,
        disagg_threshold=2 * BS,
    )
    await service.start()
    await client.start()
    faults.set_injector(
        faults.FaultInjector(faults.FaultSpec(corrupt_kv="bits", every=1))
    )
    try:
        got = []
        async for out in decode.generate(_req(prompt, 8), Context()):
            assert out.error is None, out.error
            got.extend(out.token_ids)
        assert got == ref
        # frames were shipped but every one was refused at land time
        assert service.stats.frames_tx >= 3
        assert integrity.COUNTERS.failures.get("disagg_frame", 0) >= 3
        assert integrity.COUNTERS.failures.get("disagg_final", 0) >= 1
        assert decode.kv_frames_rx == 0  # nothing corrupt ever landed
    finally:
        faults.set_injector(None)
        await decode.close()
        await client.close()
        await service.close()
        await fabric.close()


async def test_fenced_prefill_frames_refused():
    """Frames stamped with a fenced epoch are dropped and the final
    response degrades to a `fenced` error (requester recomputes)."""
    fabric = FabricClient.in_process(FabricState())
    ns = "integ-fence-stream"
    fences = FenceRegistry(fabric)
    await fences.start()
    await fences.fence(0xDEAD)
    prefill = MockPrefillEngine(
        MockEngineArgs(block_size=BS, speedup_ratio=1000.0), chunk_blocks=1
    )
    service = PrefillWorkerService(
        fabric, ns, prefill, stamp=make_stamp(0xDEAD, 0xDEAD)
    )
    client = RemotePrefillClient(
        fabric, ns, block_size=BS, timeout=10, fences=fences
    )
    await service.start()
    await client.start()
    try:
        resp = await client.prefill(list(range(2, 2 + 3 * BS)), stream=True,
                                    on_frame=_fail_on_frame)
        assert resp.code == "fenced" and resp.payload is None
        assert integrity.COUNTERS.fenced_rejects.get("kv_stream", 0) >= 1
    finally:
        await client.close()
        await service.close()
        await fences.close()
        await fabric.close()


async def _fail_on_frame(frame):  # pragma: no cover - must never run
    raise AssertionError("fenced frame reached the land path")


# ---------------------------------------------------- epoch fencing


async def test_lease_expiry_writes_fence_tombstone():
    state = FabricState()
    fabric = FabricClient.in_process(state)
    fences = FenceRegistry(fabric)
    await fences.start()
    lease = await fabric.lease_grant(0.2)
    state.start()
    deadline = time.monotonic() + 5.0
    while not fences.is_fenced(lease):
        assert time.monotonic() < deadline, "tombstone never appeared"
        await asyncio.sleep(0.05)
    raw = await fabric.kv_get(fence_key(lease))
    assert raw == b"lease_expired"
    # graceful revoke must NOT fence
    lease2 = await fabric.lease_grant(10.0)
    await fabric.lease_revoke(lease2)
    await asyncio.sleep(0.1)
    assert not fences.is_fenced(lease2)
    assert await fabric.kv_get(fence_key(lease2)) is None
    await fences.close()
    await state.close()
    await fabric.close()


async def test_zombie_partition_self_fences_engine():
    """DYN_FAULT=zombie_partition: keepalives are swallowed while the
    cluster expires the lease; when the window ends, the next keepalive
    reports the lease dead and the runtime's on_fence hook fails every
    lane with a structured worker_fenced error."""
    faults.set_injector(
        faults.FaultInjector(faults.FaultSpec(zombie_partition_s=0.6))
    )
    drt = await DistributedRuntime.detached(
        config=RuntimeConfig(lease_ttl_s=0.3), state=FabricState()
    )
    engine = MockEngine(
        MockEngineArgs(block_size=BS, speedup_ratio=2.0)
    )
    fence_reasons = []

    def _on_fence(reason: str) -> None:
        fence_reasons.append(reason)
        engine.fence(reason)

    drt.on_fence(_on_fence)
    try:
        finals = []

        async def consume():
            async for out in engine.generate(
                _req(list(range(2, 10)), 10_000), Context()
            ):
                if out.finish_reason is not None:
                    finals.append(out)

        task = asyncio.create_task(consume())
        deadline = time.monotonic() + 10.0
        while not drt.fenced:
            assert time.monotonic() < deadline, "runtime never self-fenced"
            await asyncio.sleep(0.05)
        await asyncio.wait_for(task, 5.0)
        # the in-flight stream ended with the structured fence error
        assert finals and finals[0].error is not None
        assert finals[0].error["code"] == "worker_fenced"
        assert fence_reasons and "lease" in fence_reasons[0]
        assert engine.fenced
        # KV conserved through the fence teardown
        assert engine.active == [] and len(engine.waiting) == 0
        assert all(n == 0 for n in engine.cache.refs.values())
        # new work is refused with the same structured code
        out = [o async for o in engine.generate(_req([1, 2], 4), Context())]
        assert out[-1].error["code"] == "worker_fenced"
        # the death certificate reached the fabric (cluster side wrote it
        # on expiry; the runtime best-efforts its own copy too)
        raw = await drt.fabric.kv_get(fence_key(drt.fencing_epoch))
        assert raw in (b"lease_expired", b"self_fenced")
    finally:
        faults.set_injector(None)
        await engine.close()
        await drt.close()


class _FakeStream:
    def __init__(self, items):
        self._items = list(items)

    def __aiter__(self):
        async def gen():
            for it in self._items:
                yield it

        return gen()

    async def close(self):
        pass


async def test_remote_engine_rejects_fenced_stamp_and_migrates():
    """Dispatch-plane fencing: a zombie worker's stamped tokens are
    refused mid-stream and the request replays onto a healthy worker."""
    from dynamo_tpu.discovery import RemoteEngine

    fabric = FabricClient.in_process(FabricState())
    fences = FenceRegistry(fabric)
    await fences.start()
    await fences.fence(0xBAD)

    zombie_stamp = make_stamp(0xBAD, 0xBAD)
    live_stamp = make_stamp(0x60D, 0x60D)

    class FakeRouter:
        def __init__(self):
            self.calls = 0
            self.client = None

        async def generate(self, req, ctx, exclude=None):
            self.calls += 1
            if self.calls == 1:
                ctx.metadata["worker_instance_id"] = 0xBAD
                return _FakeStream([
                    Annotated.from_data(
                        {"token_ids": [5], "stamp": zombie_stamp}
                    ),
                ])
            ctx.metadata["worker_instance_id"] = 0x60D
            # replay carries the originally-emitted tokens? the zombie's
            # token was REJECTED, so nothing was emitted: the healthy
            # worker serves from scratch
            assert "resume_prompt_len" not in (req.get("extra") or {})
            return _FakeStream([
                Annotated.from_data(
                    {"token_ids": [7, 8], "stamp": live_stamp}
                ),
                Annotated.from_data(
                    {"token_ids": [], "finish_reason": "stop",
                     "stamp": live_stamp}
                ),
            ])

    router = FakeRouter()
    engine = RemoteEngine(router, fences=fences)
    engine.backoff_base_s = 0.001
    req = _req([1, 2, 3], 8)
    got = []
    async for out in engine(req, Context()):
        got.extend(out.token_ids)
        assert out.error is None, out.error
    assert got == [7, 8]
    assert router.calls == 2
    assert integrity.COUNTERS.fenced_rejects.get("dispatch") == 1
    await fences.close()
    await fabric.close()


async def test_metrics_aggregator_skips_fenced_publishers():
    drt = await DistributedRuntime.detached(state=FabricState())
    try:
        eid = EndpointId("integ", "backend", "generate")
        comp = drt.namespace("integ").component("backend")
        good = ForwardPassMetrics(worker_stats=WorkerStats(
            request_total_slots=4,
            integrity_failures_by_path={"tier_host": 2},
            num_blocks_quarantined=1,
            fenced_rejects_by_plane={"kv_stream": 3},
        ))
        zombie = ForwardPassMetrics(worker_stats=WorkerStats(
            request_total_slots=100,
        ))
        await drt.fabric.kv_put(
            stats_key(eid, 1),
            msgpack.packb(
                {**good.to_dict(), "stamp": make_stamp(1, 1)},
                use_bin_type=True,
            ),
        )
        await drt.fabric.kv_put(
            stats_key(eid, 2),
            msgpack.packb(
                {**zombie.to_dict(), "stamp": make_stamp(2, 2)},
                use_bin_type=True,
            ),
        )
        fences = await drt.fences()
        await fences.fence(2)
        agg = KvMetricsAggregator(comp, eid)
        per_worker = await agg.collect()
        assert set(per_worker) == {1}  # zombie publish skipped
        assert integrity.COUNTERS.fenced_rejects.get("metrics") == 1
        merged = await agg.aggregate(per_worker)
        # integrity fields survive the merge
        ws = merged.worker_stats
        assert ws.integrity_failures_by_path == {"tier_host": 2}
        assert ws.num_blocks_quarantined == 1
        assert ws.fenced_rejects_by_plane == {"kv_stream": 3}
    finally:
        await drt.close()


# ------------------------------------------------------- wire version


async def test_wire_version_mismatch_is_structured():
    reader = asyncio.StreamReader()
    reader.feed_data(wire.pack([1, "op", {}], version=9))
    with pytest.raises(wire.WireVersionError) as ei:
        await wire.read_frame(reader)
    assert ei.value.got == 9
    assert ei.value.want == (wire.WIRE_MIN, wire.WIRE_MAX)
    msg = str(ei.value)
    assert "v9" in msg and f"v{wire.WIRE_MIN}..v{wire.WIRE_MAX}" in msg
    assert "mismatch" in msg
    # same-version frames still round-trip
    reader2 = asyncio.StreamReader()
    reader2.feed_data(wire.pack([1, "op", {"a": 1}]))
    assert await wire.read_frame(reader2) == [1, "op", {"a": 1}]


async def test_skewed_peer_fails_handshake_with_friendly_error():
    """A fabric server speaking a wire version outside our negotiable
    range: the client's handshake raises the structured mismatch at
    connect time (no hang, no failover spin, no call ever dispatched)."""

    async def skewed_server(reader, writer):
        with contextlib.suppress(Exception):
            await wire.read_frame(reader)  # accept the hello
        writer.write(wire.pack([1, "ok", 42], version=9))
        with contextlib.suppress(Exception):
            await writer.drain()
        # Server.wait_closed() (3.12+) waits for every connection
        writer.close()

    server = await asyncio.start_server(skewed_server, "127.0.0.1", 0)
    port = server.sockets[0].getsockname()[1]
    with pytest.raises(ConnectionError) as ei:
        await FabricClient.connect(f"127.0.0.1:{port}")
    assert "mismatch" in str(ei.value) and "v9" in str(ei.value)
    server.close()
    await server.wait_closed()


# ----------------------------------------------------------- backoff


def test_backoff_full_jitter_bounds_and_budget():
    rolls = iter([0.5, 1.0, 0.25, 1.0, 1.0, 1.0])
    b = Backoff(base_s=0.1, cap_s=0.35, rng=lambda: next(rolls),
                max_attempts=4)
    assert b.next_delay() == pytest.approx(0.05)  # 0.1 * 0.5
    assert b.next_delay() == pytest.approx(0.2)  # 0.2 * 1.0
    assert b.next_delay() == pytest.approx(0.35 * 0.25)  # capped ceiling
    assert b.next_delay() == pytest.approx(0.35)
    assert b.next_delay() is None  # attempts exhausted
    b.reset()
    assert b.attempts == 0 and b.next_delay() is not None

    # wall-clock budget
    clock = [0.0]
    bb = Backoff(base_s=0.1, budget_s=1.0, rng=lambda: 1.0,
                 clock=lambda: clock[0])
    assert bb.next_delay() is not None
    clock[0] = 2.0
    assert bb.next_delay() is None

    # stateless helper used by the migration replay
    for attempt, ceiling in ((1, 0.05), (2, 0.1), (3, 0.2), (10, 2.0)):
        d = full_jitter_delay(attempt, 0.05, cap_s=2.0, rng=lambda: 1.0)
        assert d == pytest.approx(ceiling)
        assert full_jitter_delay(attempt, 0.05, rng=lambda: 0.0) == 0.0
