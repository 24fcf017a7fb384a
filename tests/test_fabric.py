"""Fabric state machine + TCP server/client tests (kv, leases, watch,
pub/sub queue groups, work queue, object store)."""

import asyncio

import pytest

from dynamo_tpu.fabric import FabricClient, FabricServer
from dynamo_tpu.fabric.state import FabricState, subject_matches


def test_subject_matching():
    assert subject_matches("a.b.c", "a.b.c")
    assert subject_matches("a.*.c", "a.b.c")
    assert subject_matches("a.>", "a.b.c")
    assert subject_matches(">", "anything.at.all")
    assert not subject_matches("a.>", "a")  # '>' needs >=1 token (NATS)
    assert not subject_matches("a.b", "a.b.c")
    assert not subject_matches("a.b.c", "a.b")
    assert not subject_matches("a.*.x", "a.b.c")


@pytest.mark.asyncio
async def test_kv_put_get_delete_prefix():
    c = FabricClient.in_process(FabricState())
    await c.kv_put("instances/ns/a/ep:1", b"one")
    await c.kv_put("instances/ns/a/ep:2", b"two")
    await c.kv_put("other/key", b"x")
    assert await c.kv_get("instances/ns/a/ep:1") == b"one"
    assert await c.kv_get("missing") is None
    pfx = await c.kv_get_prefix("instances/ns/a/")
    assert set(pfx) == {"instances/ns/a/ep:1", "instances/ns/a/ep:2"}
    assert await c.kv_delete("instances/ns/a/ep:1")
    assert not await c.kv_delete("instances/ns/a/ep:1")
    assert await c.kv_delete_prefix("instances/") == 1


@pytest.mark.asyncio
async def test_kv_create_cas():
    c = FabricClient.in_process(FabricState())
    assert await c.kv_create("k", b"v1")
    assert await c.kv_create("k", b"v1")  # same value validates
    assert not await c.kv_create("k", b"v2")  # different value fails


@pytest.mark.asyncio
async def test_lease_expiry_removes_keys_and_notifies_watch():
    c = FabricClient.in_process(FabricState())
    lease = await c.lease_grant(0.6)
    await c.kv_put("instances/x", b"v", lease_id=lease)
    watch = await c.watch_prefix("instances/")
    assert [ev.key for ev in watch.initial] == ["instances/x"]
    # no keepalive -> janitor expires the lease and deletes the key
    ev = await asyncio.wait_for(watch.__anext__(), timeout=3.0)
    assert ev.type == "delete" and ev.key == "instances/x"
    await watch.cancel()


@pytest.mark.asyncio
async def test_lease_keepalive_keeps_key():
    c = FabricClient.in_process(FabricState())
    lease = await c.lease_grant(0.6)
    await c.kv_put("k", b"v", lease_id=lease)
    for _ in range(4):
        await asyncio.sleep(0.3)
        assert await c.lease_keepalive(lease)
    assert await c.kv_get("k") == b"v"
    await c.lease_revoke(lease)
    assert await c.kv_get("k") is None


@pytest.mark.asyncio
async def test_a_held_loop_costs_no_lease_but_a_missed_keepalive_does():
    """A process whose interpreter is held for longer than a lease's TTL (a
    profiler closing its window held one for ten seconds on the chip and the
    server fenced itself) held its store with it: the janitor gives every
    lease the time it was held back. A lease nobody refreshes still expires."""
    import time

    state = FabricState()
    c = FabricClient.in_process(state)
    lease = await c.lease_grant(1.0)
    await c.kv_put("instances/held", b"v", lease_id=lease)
    await asyncio.sleep(0.6)  # the janitor sleeps; a keepalive lands in time
    assert await c.lease_keepalive(lease)
    time.sleep(2.5)  # the loop is held for two and a half TTLs
    await asyncio.sleep(0.05)  # the janitor wakes before any keepalive
    assert await c.kv_get("instances/held") == b"v"
    assert await c.lease_keepalive(lease)
    # and without a keepalive, with the loop running, it goes as before
    watch = await c.watch_prefix("instances/")
    ev = await asyncio.wait_for(watch.__anext__(), timeout=4.0)
    assert ev.type == "delete" and ev.key == "instances/held"
    assert not await c.lease_keepalive(lease)
    await watch.cancel()


@pytest.mark.asyncio
async def test_watch_streams_puts_and_deletes():
    c = FabricClient.in_process(FabricState())
    watch = await c.watch_prefix("p/")
    await c.kv_put("p/a", b"1")
    await c.kv_put("q/b", b"2")  # outside prefix: not delivered
    await c.kv_delete("p/a")
    ev1 = await asyncio.wait_for(watch.__anext__(), 1)
    ev2 = await asyncio.wait_for(watch.__anext__(), 1)
    assert (ev1.type, ev1.key, ev1.value) == ("put", "p/a", b"1")
    assert (ev2.type, ev2.key) == ("delete", "p/a")
    await watch.cancel()


@pytest.mark.asyncio
async def test_pubsub_broadcast_and_queue_group():
    c = FabricClient.in_process(FabricState())
    b1 = await c.subscribe("evt.x")
    b2 = await c.subscribe("evt.>")
    g1 = await c.subscribe("evt.x", group="g")
    g2 = await c.subscribe("evt.x", group="g")
    n = await c.publish("evt.x", b"m1")
    assert n == 3  # two broadcasts + one group member
    assert (await b1.next(1))[1] == b"m1"
    assert (await b2.next(1))[1] == b"m1"
    # group delivery round-robins between members
    await c.publish("evt.x", b"m2")
    got = []
    for sub in (g1, g2):
        item = await sub.next(0.2)
        if item:
            got.append(item[1])
    assert sorted(got) == [b"m1", b"m2"]


@pytest.mark.asyncio
async def test_work_queue_ack_and_redeliver():
    state = FabricState()
    c = FabricClient.in_process(state)
    state._queue("q").redeliver_after = 0.6  # fast redelivery for the test
    await c.queue_put("q", b"job1")
    assert await c.queue_depth("q") == 1
    msg = await c.queue_pop("q", timeout=1)
    assert msg is not None and msg[1] == b"job1"
    # unacked -> redelivered after timeout
    again = await c.queue_pop("q", timeout=3)
    assert again is not None and again[1] == b"job1"
    assert await c.queue_ack("q", again[0])
    assert await c.queue_depth("q") == 0
    assert await c.queue_pop("q", timeout=0.1) is None


@pytest.mark.asyncio
async def test_object_store():
    c = FabricClient.in_process(FabricState())
    await c.obj_put("models", "card.json", b"{}")
    assert await c.obj_get("models", "card.json") == b"{}"
    assert await c.obj_list("models") == ["card.json"]
    assert await c.obj_delete("models", "card.json")
    assert await c.obj_get("models", "card.json") is None


@pytest.mark.asyncio
async def test_remote_fabric_over_tcp():
    server = FabricServer("127.0.0.1", 0)
    await server.start()
    try:
        c1 = await FabricClient.connect(server.addr)
        c2 = await FabricClient.connect(server.addr)
        # kv visible across clients
        await c1.kv_put("shared/k", b"v")
        assert await c2.kv_get("shared/k") == b"v"
        # watch across clients
        watch = await c2.watch_prefix("shared/")
        assert len(watch.initial) == 1
        await c1.kv_put("shared/k2", b"v2")
        ev = await asyncio.wait_for(watch.__anext__(), 2)
        assert ev.key == "shared/k2" and ev.value == b"v2"
        await watch.cancel()
        # pub/sub across clients
        sub = await c2.subscribe("topic.a")
        await asyncio.sleep(0.05)
        assert await c1.publish("topic.a", b"hello") == 1
        item = await sub.next(2)
        assert item == ("topic.a", b"hello")
        await sub.unsubscribe()
        # queue across clients
        await c1.queue_put("wq", b"task")
        msg = await c2.queue_pop("wq", timeout=2)
        assert msg is not None and msg[1] == b"task"
        assert await c2.queue_ack("wq", msg[0])
        # leases
        lease = await c1.lease_grant(5.0)
        await c1.kv_put("leased", b"x", lease_id=lease)
        assert await c1.lease_keepalive(lease)
        await c1.lease_revoke(lease)
        assert await c2.kv_get("leased") is None
        await c1.close()
        await c2.close()
    finally:
        await server.close()
