"""Internal RPC stack tests.

Mirrors the reference's rpc loopback integration tests
(rpc/test/rpc_gen_cycling_test.cc): an echo-style service round-trips
requests over a real socket, exercising checksums, concurrent correlation,
missing-method status, server errors, compression, reconnect backoff, and
per-method failure probes.
"""

from __future__ import annotations

import asyncio

import pytest

from redpanda_tpu import rpc
from redpanda_tpu.finjector import ProbeTriggered, honey_badger
from redpanda_tpu.rpc import serde, wire
from redpanda_tpu.rpc.transport import RpcError, Transport, TransportClosed


def run(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


# ---------------------------------------------------------------- serde
def test_serde_scalar_roundtrip():
    s = serde.S(
        ("a", serde.I32),
        ("b", serde.I64),
        ("c", serde.STRING),
        ("d", serde.BYTES),
        ("e", serde.Vector(serde.I16)),
        ("f", serde.Optional(serde.STRING)),
        ("g", serde.Map(serde.STRING, serde.I32)),
        ("h", serde.BOOL),
    )
    msg = {
        "a": -7, "b": 1 << 40, "c": "héllo", "d": b"\x00\xff",
        "e": [1, 2, 3], "f": None, "g": {"x": 1, "y": 2}, "h": True,
    }
    assert s.decode(s.encode(msg)) == msg


def test_serde_nested_struct_and_envelope():
    inner = serde.S(("x", serde.I32), ("y", serde.STRING))
    env = serde.Envelope(serde.S(("items", serde.Vector(inner))), version=1)
    msg = {"items": [{"x": 1, "y": "a"}, {"x": 2, "y": "b"}]}
    assert env.decode(env.encode(msg)) == msg


def test_envelope_compat_rejection():
    env_v0 = serde.Envelope(serde.S(("x", serde.I32)), version=0)
    blob = serde.Envelope(serde.S(("x", serde.I32)), version=3, compat_version=2).encode({"x": 1})
    with pytest.raises(serde.SerdeError):
        env_v0.decode(blob)


# ---------------------------------------------------------------- wire
def test_header_roundtrip_and_corruption():
    h = wire.Header(compression=0, payload_size=10, meta=0xDEAD, correlation_id=7, payload_checksum=123)
    raw = bytearray(h.encode())
    assert wire.Header.decode(bytes(raw)).meta == 0xDEAD
    raw[10] ^= 0xFF  # corrupt a post-checksum byte
    with pytest.raises(wire.WireError):
        wire.Header.decode(bytes(raw))


def test_frame_compression_roundtrip():
    payload = b"z" * 4096
    framed = wire.frame(payload, meta=1, correlation_id=2, compress=True)
    h = wire.Header.decode(framed[: wire.HEADER_SIZE])
    assert h.compression == wire.COMPRESSION_ZSTD
    assert h.payload_size < len(payload)
    assert wire.open_payload(h, framed[wire.HEADER_SIZE :]) == payload


# ---------------------------------------------------------------- service defs
ECHO_REQ = serde.S(("text", serde.STRING))
ECHO_RESP = serde.S(("text", serde.STRING))
SLEEP_REQ = serde.S(("ms", serde.I32))

echo_service = rpc.ServiceDef(
    "cycling", "echo",
    [
        rpc.MethodDef("echo", ECHO_REQ, ECHO_RESP),
        rpc.MethodDef("echo_twice", ECHO_REQ, ECHO_RESP),
        rpc.MethodDef("sleep_for", SLEEP_REQ, ECHO_RESP),
        rpc.MethodDef("fail", ECHO_REQ, ECHO_RESP),
    ],
)


class EchoImpl:
    async def echo(self, req):
        return {"text": req["text"]}

    async def echo_twice(self, req):
        return {"text": req["text"] * 2}

    async def sleep_for(self, req):
        await asyncio.sleep(req["ms"] / 1000)
        return {"text": "zzz"}

    async def fail(self, req):
        raise RuntimeError("boom")


def test_method_ids_stable_and_distinct():
    ids = [m.id for m in echo_service.methods.values()]
    assert len(set(ids)) == len(ids)
    again = rpc.ServiceDef(
        "cycling", "echo", [rpc.MethodDef("echo", ECHO_REQ, ECHO_RESP)]
    )
    assert again.methods["echo"].id == echo_service.methods["echo"].id


async def _with_server(fn):
    server = rpc.Server()
    proto = rpc.SimpleProtocol()
    proto.register_service(rpc.ServiceHandler(echo_service, EchoImpl()))
    server.set_protocol(proto)
    await server.start()
    t = Transport("127.0.0.1", server.port)
    await t.connect()
    try:
        return await fn(server, t)
    finally:
        await t.close()
        await server.stop()


def test_echo_roundtrip():
    async def go(server, t):
        client = rpc.Client(echo_service, t)
        assert (await client.echo({"text": "hi"}))["text"] == "hi"
        assert (await client.echo_twice({"text": "ab"}))["text"] == "abab"

    run(_with_server(go))


def test_concurrent_requests_preserve_correlation():
    async def go(server, t):
        client = rpc.Client(echo_service, t)
        slow = asyncio.ensure_future(client.sleep_for({"ms": 100}))
        fast = [client.echo({"text": f"r{i}"}) for i in range(16)]
        results = await asyncio.gather(*fast)
        assert [r["text"] for r in results] == [f"r{i}" for i in range(16)]
        assert (await slow)["text"] == "zzz"

    run(_with_server(go))


def test_trace_ctx_frame_roundtrip():
    ctx = wire.TraceContext(0xABCDEF0123, 0x42, True)
    framed = wire.frame(b"payload", meta=9, correlation_id=3, trace_ctx=ctx)
    h = wire.Header.decode(framed[: wire.HEADER_SIZE])
    assert h.version == wire.VERSION_TRACE_CTX
    got = wire.TraceContext.decode(
        framed[wire.HEADER_SIZE : wire.HEADER_SIZE + wire.TRACE_CTX_SIZE]
    )
    assert got == ctx
    body = framed[wire.HEADER_SIZE + wire.TRACE_CTX_SIZE :]
    assert wire.open_payload(h, body) == b"payload"
    with pytest.raises(wire.WireError):
        wire.TraceContext.decode(b"short")


def test_no_trace_ctx_adds_zero_wire_bytes():
    """The propagation header is feature-flagged on the tracer: without a
    sampled trace the frame is the classic version-0 layout byte-for-byte
    — a disabled tracer costs NOTHING on the wire."""
    plain = wire.frame(b"x" * 100, meta=1, correlation_id=7)
    assert len(plain) == wire.HEADER_SIZE + 100
    assert wire.Header.decode(plain[: wire.HEADER_SIZE]).version == 0

    async def go(server, t):
        from redpanda_tpu.observability import tracer

        assert not tracer.enabled  # default posture in the test process
        client = rpc.Client(echo_service, t)
        assert (await client.echo({"text": "hi"}))["text"] == "hi"

    run(_with_server(go))


def test_server_joins_sampled_trace_never_roots():
    """A sampled request's context rides the wire and the server opens a
    JOINed rpc.handle span under the SAME trace id, anchored to the
    sender's rpc.send span; an unsampled request (no ambient trace) adds
    no bytes and mints no orphan trace."""
    from redpanda_tpu.observability import tracer

    async def go(server, t):
        client = rpc.Client(echo_service, t)
        tracer.configure(enabled=True)
        tracer.reset()
        try:
            with tracer.span("test.root", root=True) as root:
                await client.echo({"text": "sampled"})
            # outside any span: unsampled, must not create traces
            await client.echo({"text": "unsampled"})
            spans = [s for tr in tracer.recent(0) for s in tr["spans"]]
            sends = [s for s in spans if s["name"] == "rpc.send"]
            handles = [s for s in spans if s["name"] == "rpc.handle"]
            assert len(sends) == 1 and len(handles) == 1
            assert sends[0]["trace_id"] == root.trace_id
            assert handles[0]["trace_id"] == root.trace_id  # JOINed
            assert handles[0]["parent_span"] == sends[0]["span_id"]
            # no orphan trace exists for the unsampled echo
            tids = {s["trace_id"] for s in spans}
            assert tids == {root.trace_id}
        finally:
            tracer.configure(enabled=False)
            tracer.reset()

    run(_with_server(go))


def test_unknown_method_404():
    async def go(server, t):
        with pytest.raises(RpcError) as ei:
            await t.send(0xDEADBEEF, b"")
        assert ei.value.status == wire.STATUS_METHOD_NOT_FOUND

    run(_with_server(go))


def test_handler_exception_500():
    async def go(server, t):
        client = rpc.Client(echo_service, t)
        with pytest.raises(RpcError) as ei:
            await client.fail({"text": "x"})
        assert ei.value.status == wire.STATUS_SERVER_ERROR

    run(_with_server(go))


def test_client_timeout_408():
    async def go(server, t):
        client = rpc.Client(echo_service, t)
        with pytest.raises(RpcError) as ei:
            await client.sleep_for({"ms": 2000}, timeout=0.05)
        assert ei.value.status == wire.STATUS_REQUEST_TIMEOUT

    run(_with_server(go))


def test_reconnect_transport_recovers():
    async def go():
        server = rpc.Server()
        proto = rpc.SimpleProtocol()
        proto.register_service(rpc.ServiceHandler(echo_service, EchoImpl()))
        server.set_protocol(proto)
        await server.start()
        port = server.port
        rt = rpc.ReconnectTransport("127.0.0.1", port, rpc.BackoffPolicy(base_ms=1))
        client = rpc.Client(echo_service, rt)
        assert (await client.echo({"text": "a"}))["text"] == "a"
        await server.stop()
        with pytest.raises((TransportClosed, RpcError)):
            await client.echo({"text": "b"})
        # restart on the same port; transport reconnects
        server2 = rpc.Server(port=port)
        server2.set_protocol(proto)
        await server2.start()
        for _ in range(20):
            try:
                assert (await client.echo({"text": "c"}))["text"] == "c"
                break
            except (TransportClosed, RpcError):
                await asyncio.sleep(0.02)
        else:
            raise AssertionError("never reconnected")
        await rt.close()
        await server2.stop()

    run(go())


def test_failure_probe_injects_exception():
    async def go(server, t):
        honey_badger.enable()
        honey_badger.set_exception("echo", "echo")
        client = rpc.Client(echo_service, t)
        try:
            with pytest.raises(RpcError) as ei:
                await client.echo({"text": "x"})
            assert ei.value.status == wire.STATUS_SERVER_ERROR
            honey_badger.unset("echo", "echo")
            assert (await client.echo({"text": "x"}))["text"] == "x"
        finally:
            honey_badger.disable()

    run(_with_server(go))


def test_probe_registry_lists_methods():
    mods = honey_badger.modules()
    assert "echo" in mods and "sleep_for" in mods["echo"]


def test_connection_cache_shard_assignment():
    cc = rpc.ConnectionCache(n_shards=8)
    shards = {cc.shard_for(n) for n in range(64)}
    assert shards <= set(range(8)) and len(shards) > 1


def test_tron_style_soak_with_connection_churn():
    """Soak the RPC stack the way the reference's tron echo tool does
    (src/v/raft/tron): many concurrent echo clients hammer one server
    while connections are periodically torn down mid-flight; every
    response must match its request (correlation never crosses wires)
    and the server must end the run with zero leaked connections."""

    async def go():
        server = rpc.Server()
        proto = rpc.SimpleProtocol()
        proto.register_service(rpc.ServiceHandler(echo_service, EchoImpl()))
        server.set_protocol(proto)
        await server.start()

        N_CLIENTS = 8
        OPS = 60
        errors: list[str] = []

        async def soak_client(cid: int):
            rt = rpc.ReconnectTransport(
                "127.0.0.1", server.port, rpc.BackoffPolicy(base_ms=1)
            )
            client = rpc.Client(echo_service, rt)
            done = 0
            for i in range(OPS):
                text = f"c{cid}-{i}"
                try:
                    resp = await client.echo({"text": text})
                    if resp["text"] != text:
                        errors.append(f"cross-talk: sent {text} got {resp['text']}")
                    done += 1
                except (TransportClosed, RpcError, OSError):
                    pass  # churn window: retried ops are not required
                # churn: every 17th op this client drops its own socket
                if i % 17 == 16:
                    await rt.close()
            await rt.close()
            return done

        totals = await asyncio.gather(*(soak_client(c) for c in range(N_CLIENTS)))
        # all client sockets are closed: the server's connection handlers
        # must all have drained (no leaked connection tasks)
        for _ in range(50):
            if not server._conn_tasks:
                break
            await asyncio.sleep(0.1)
        leaked = len(server._conn_tasks)
        await server.stop()
        assert leaked == 0, f"{leaked} server connection task(s) leaked"
        assert not errors, errors[:5]
        # the vast majority of ops complete despite the churn
        assert sum(totals) >= N_CLIENTS * OPS * 0.8, totals

    asyncio.run(asyncio.wait_for(go(), 120))
