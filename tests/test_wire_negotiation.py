"""Negotiated fabric wire versioning (ISSUE 18): hello handshake pins the
highest common version; honest-skew coverage against fake peers in BOTH
directions (older server / newer client, newer server / older client), and
the ignore-unknown-trailing-fields compatibility contract."""

import asyncio

import msgpack
import pytest

from dynamo_tpu.fabric import FabricClient, FabricServer
from dynamo_tpu.fabric import wire


# ---------------------------------------------------------------- helpers


def _pack_at(version: int, msg) -> bytes:
    body = msgpack.packb(msg, use_bin_type=True)
    return bytes([version]) + len(body).to_bytes(4, "big") + body


async def _read_raw(reader: asyncio.StreamReader) -> tuple[int, object]:
    """(version_byte, body) without any version check — the fake peers
    must observe exactly what the real implementation put on the wire."""
    header = await reader.readexactly(5)
    length = int.from_bytes(header[1:], "big")
    body = await reader.readexactly(length)
    return header[0], msgpack.unpackb(body, raw=False)


class _FakeLegacyServer:
    """A pre-negotiation (v2-only) fabric server: hard-rejects any frame
    whose version byte != 2 and answers `hello` with the unknown-op error
    — byte-exact with what a PR-8-era build does."""

    def __init__(self) -> None:
        self.addr = ""
        self.seen_versions: list[int] = []
        self._server = None

    async def start(self) -> None:
        self._server = await asyncio.start_server(
            self._handle, "127.0.0.1", 0
        )
        host, port = self._server.sockets[0].getsockname()[:2]
        self.addr = f"{host}:{port}"

    async def close(self) -> None:
        self._server.close()
        await self._server.wait_closed()

    async def _handle(self, reader, writer) -> None:
        kv: dict = {}
        try:
            while True:
                version, msg = await _read_raw(reader)
                self.seen_versions.append(version)
                if version != 2:  # v2-only build: hard reject
                    break
                req_id, op, a = msg
                if op == "hello":
                    reply = [req_id, "err", f"ValueError: unknown op {op!r}"]
                elif op == "ping":
                    reply = [req_id, "ok", "pong"]
                elif op == "kv_put":
                    kv[a["key"]] = a["value"]
                    reply = [req_id, "ok", None]
                elif op == "kv_get":
                    reply = [req_id, "ok", kv.get(a["key"])]
                else:
                    reply = [req_id, "err", f"ValueError: unknown op {op!r}"]
                writer.write(_pack_at(2, reply))
                await writer.drain()
        except (asyncio.IncompleteReadError, ConnectionResetError):
            pass
        finally:
            writer.close()


# ------------------------------------------------------- negotiate() unit


def test_negotiate_picks_highest_common():
    assert wire.negotiate(2, 3) == wire.WIRE_MAX
    assert wire.negotiate(2, 2) == 2
    # a future peer supporting [2, 99] clamps down to OUR max
    assert wire.negotiate(2, 99) == wire.WIRE_MAX
    # a future peer whose floor is inside our range pins its floor-or-above
    assert wire.negotiate(wire.WIRE_MAX, 99) == wire.WIRE_MAX


def test_negotiate_disjoint_raises_structured():
    with pytest.raises(wire.WireVersionError) as ei:
        wire.negotiate(wire.WIRE_MAX + 1, wire.WIRE_MAX + 3)
    assert isinstance(ei.value, ConnectionError)
    assert ei.value.got == wire.WIRE_MAX + 3
    with pytest.raises(wire.WireVersionError):
        wire.negotiate(0, wire.WIRE_MIN - 1)


def test_read_frame_accepts_whole_range_rejects_outside():
    async def run():
        for v in range(wire.WIRE_MIN, wire.WIRE_MAX + 1):
            reader = asyncio.StreamReader()
            reader.feed_data(_pack_at(v, ["x"]))
            assert await wire.read_frame(reader) == ["x"]
        for v in (wire.WIRE_MIN - 1, wire.WIRE_MAX + 1, 99):
            reader = asyncio.StreamReader()
            reader.feed_data(_pack_at(v, ["x"]))
            with pytest.raises(wire.WireVersionError) as ei:
                await wire.read_frame(reader)
            assert ei.value.got == v

    asyncio.get_event_loop_policy().new_event_loop().run_until_complete(run())


# ------------------------------------------------ real server, new client


@pytest.mark.asyncio
async def test_hello_pins_highest_common_version():
    server = FabricServer("127.0.0.1", 0)
    await server.start()
    try:
        c = await FabricClient.connect(server.addr)
        assert c.wire_version == wire.WIRE_MAX
        assert c.status()["wire_version"] == wire.WIRE_MAX
        # the pinned connection round-trips ops + watches normally
        await c.kv_put("neg/k", b"v")
        assert await c.kv_get("neg/k") == b"v"
        watch = await c.watch_prefix("neg/")
        await c.kv_put("neg/k2", b"v2")
        ev = await asyncio.wait_for(watch.__anext__(), 2)
        assert ev.key == "neg/k2"
        await watch.cancel()
        await c.close()
    finally:
        await server.close()


@pytest.mark.asyncio
async def test_legacy_client_against_new_server_stays_at_floor():
    """Direction: NEW server, OLD client. An old client never sends hello
    — the server must keep its replies at the v2 floor."""
    server = FabricServer("127.0.0.1", 0)
    await server.start()
    try:
        host, _, port = server.addr.rpartition(":")
        reader, writer = await asyncio.open_connection(host, int(port))
        writer.write(_pack_at(2, [1, "ping", {}]))
        writer.write(_pack_at(2, [2, "kv_put", {"key": "a", "value": b"1"}]))
        writer.write(_pack_at(2, [3, "kv_get", {"key": "a"}]))
        await writer.drain()
        replies = {}
        for _ in range(3):
            version, msg = await _read_raw(reader)
            assert version == 2, "reply to an un-negotiated client left v2"
            replies[msg[0]] = msg[1:]
        assert replies[1] == ["ok", "pong"]
        assert replies[3] == ["ok", b"1"]
        writer.close()
    finally:
        await server.close()


# ------------------------------------------------ fake server, both skews


@pytest.mark.asyncio
async def test_new_client_against_legacy_server_pins_floor():
    """Direction: OLD server, NEW client. hello gets unknown-op; the
    client pins v2 and every frame it ever sends stays at v2."""
    fake = _FakeLegacyServer()
    await fake.start()
    try:
        c = await FabricClient.connect(fake.addr)
        assert c.wire_version == wire.WIRE_MIN
        await c.kv_put("legacy/k", b"old")
        assert await c.kv_get("legacy/k") == b"old"
        assert set(fake.seen_versions) == {2}
        await c.close()
    finally:
        await fake.close()


@pytest.mark.asyncio
async def test_disjoint_range_fails_loudly_not_garbage():
    """A peer whose whole range is above ours must yield the structured
    WireVersionError from connect — not a framing parse error."""

    async def handle(reader, writer):
        try:
            _, msg = await _read_raw(reader)
            req_id = msg[0]
            writer.write(_pack_at(2, [
                req_id, "err",
                "WireVersionError: fabric wire protocol mismatch: peer "
                "speaks v3, this build supports v7..v9",
            ]))
            await writer.drain()
        except asyncio.IncompleteReadError:
            pass
        finally:
            # Server.wait_closed() (3.12+) waits for every connection
            writer.close()

    srv = await asyncio.start_server(handle, "127.0.0.1", 0)
    host, port = srv.sockets[0].getsockname()[:2]
    try:
        with pytest.raises(ConnectionError) as ei:
            await FabricClient.connect(f"{host}:{port}")
        assert "mismatch" in str(ei.value)
    finally:
        srv.close()
        await srv.wait_closed()


@pytest.mark.asyncio
async def test_server_rejects_hello_from_disjoint_future_range():
    server = FabricServer("127.0.0.1", 0)
    await server.start()
    try:
        host, _, port = server.addr.rpartition(":")
        reader, writer = await asyncio.open_connection(host, int(port))
        writer.write(_pack_at(2, [
            1, "hello", {"min": wire.WIRE_MAX + 4, "max": wire.WIRE_MAX + 6}
        ]))
        await writer.drain()
        _, msg = await _read_raw(reader)
        assert msg[1] == "err" and "WireVersionError" in msg[2]
        writer.close()
    finally:
        await server.close()


# -------------------------------------- trailing-fields contract (linted)


@pytest.mark.asyncio
async def test_server_ignores_unknown_trailing_request_fields():
    """Contract: a newer client may append fields to the request body;
    an in-range server must serve the known prefix."""
    server = FabricServer("127.0.0.1", 0)
    await server.start()
    try:
        host, _, port = server.addr.rpartition(":")
        reader, writer = await asyncio.open_connection(host, int(port))
        writer.write(_pack_at(
            2, [1, "ping", {}, {"future": "field"}, "more"]
        ))
        await writer.drain()
        _, msg = await _read_raw(reader)
        assert msg[0] == 1 and msg[1] == "ok" and msg[2] == "pong"
        writer.close()
    finally:
        await server.close()


@pytest.mark.asyncio
async def test_client_ignores_unknown_trailing_response_and_push_fields():
    """Contract: a newer server may append fields to response AND push
    bodies; the client must parse the known prefix of both."""

    async def handle(reader, writer):
        try:
            while True:
                _, msg = await _read_raw(reader)
                req_id, op = msg[0], msg[1]
                if op == "hello":
                    writer.write(_pack_at(
                        2, [req_id, "ok", {"version": wire.WIRE_MAX}]
                    ))
                elif op == "watch_create":
                    writer.write(_pack_at(
                        wire.WIRE_MAX, [req_id, "ok", [7, []], "extra"]
                    ))
                    # push with a trailing field beyond payload
                    writer.write(_pack_at(wire.WIRE_MAX, [
                        0, "push", 7,
                        {"type": "put", "key": "p/x", "value": b"1",
                         "lease_id": 0},
                        {"future": True},
                    ]))
                else:
                    writer.write(_pack_at(
                        wire.WIRE_MAX, [req_id, "ok", "pong", "extra"]
                    ))
                await writer.drain()
        except (asyncio.IncompleteReadError, ConnectionResetError):
            pass
        finally:
            writer.close()

    srv = await asyncio.start_server(handle, "127.0.0.1", 0)
    host, port = srv.sockets[0].getsockname()[:2]
    try:
        c = await FabricClient.connect(f"{host}:{port}")
        assert c.wire_version == wire.WIRE_MAX
        assert await c.kv_get("anything") == "pong"  # trailing field ignored
        watch = await c.watch_prefix("p/")
        ev = await asyncio.wait_for(watch.__anext__(), 2)
        assert ev.key == "p/x" and ev.value == b"1"
        await c.close()
    finally:
        srv.close()
        await srv.wait_closed()


# ------------------------------------------- mixed-version fleet identity


@pytest.mark.asyncio
async def test_mixed_version_clients_observe_identical_state():
    """N/N+1 skew honesty at the fabric layer: a floor-pinned (v2) client
    and a fully-negotiated client driving the SAME op sequence against
    one server observe identical results — the negotiated version changes
    framing only, never semantics."""
    server = FabricServer("127.0.0.1", 0)
    await server.start()
    try:
        new_c = await FabricClient.connect(server.addr)
        old_c = await FabricClient.connect(server.addr)
        old_c.wire_version = wire.WIRE_MIN  # simulate an N-1 build's pin
        assert new_c.wire_version == wire.WIRE_MAX

        async def drive(c: FabricClient, tag: str) -> list:
            out = []
            await c.kv_put(f"mix/{tag}", tag.encode())
            out.append(await c.kv_get(f"mix/{tag}"))
            out.append(sorted(await c.kv_get_prefix("mix/")))
            lease = await c.lease_grant(5.0)
            out.append(await c.lease_keepalive(lease))
            await c.lease_revoke(lease)
            sub = await c.subscribe("mix.topic")
            await asyncio.sleep(0.05)
            await c.publish("mix.topic", b"tok")
            out.append(await sub.next(2))
            await sub.unsubscribe()
            return out

        res_old = await drive(old_c, "a")
        res_new = await drive(new_c, "b")
        # identical shapes/semantics (keys differ only by the tag written)
        assert res_old[0] == b"a" and res_new[0] == b"b"
        assert res_old[2] == res_new[2] is True
        assert res_old[3] == ("mix.topic", b"tok")
        assert res_new[3] == ("mix.topic", b"tok")
        # both tags visible to both clients
        assert sorted(await old_c.kv_get_prefix("mix/")) == \
            sorted(await new_c.kv_get_prefix("mix/"))
        await old_c.close()
        await new_c.close()
    finally:
        await server.close()
