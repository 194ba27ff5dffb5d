"""Wire authentication: HMAC challenge-response before any pickle.loads.

Reference context: the reference speaks protobuf (no code execution on
parse); a pickle wire must authenticate peers first (round-2 review, weak #4).
"""

import asyncio
import hashlib
import hmac
import os
import pickle
import socket
import struct

import pytest

from ray_tpu.runtime import rpc


@pytest.fixture
def token():
    tok = os.urandom(32)
    rpc.set_session_token(tok)
    yield tok
    rpc.set_session_token(None)


def _run(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


def test_foreign_connection_dropped_before_unpickle(token, tmp_path):
    """A socket that can't answer the challenge never gets a frame parsed:
    a malicious pickle payload must NOT execute server-side."""
    sentinel = str(tmp_path / "pwned")

    class Evil:
        def __reduce__(self):
            return (os.system, (f"touch {sentinel}",))

    async def scenario():
        server = rpc.RpcServer()
        handled = []

        async def h(conn, **kw):
            handled.append(kw)
            return {}

        server.register("anything", h)
        await server.start()
        host, port = server.address

        # Raw foreign socket: reads the challenge, answers garbage, then
        # fires a malicious request frame.
        reader, writer = await asyncio.open_connection(host, port)
        hello = await reader.readexactly(36)
        assert hello[:3] == b"RTA"
        writer.write(b"\x00" * 32)  # wrong mac
        body = pickle.dumps((rpc.KIND_REQUEST, 1, "anything",
                             {"x": Evil()}), protocol=5)
        writer.write(struct.pack("<4sI", b"RTP\x01", len(body)) + body)
        try:
            await writer.drain()
        except ConnectionError:
            pass
        # The server must close on us without dispatching anything.
        got = await reader.read(64)
        assert got == b""  # EOF: dropped
        await asyncio.sleep(0.1)
        assert handled == []
        await server.close()

    _run(scenario())
    assert not os.path.exists(sentinel), "malicious pickle EXECUTED"


def test_wrong_token_client_rejected(token):
    async def scenario():
        server = rpc.RpcServer()

        async def h(conn, **kw):
            return {"ok": True}

        server.register("ping", h)
        await server.start()
        host, port = server.address

        reader, writer = await asyncio.open_connection(host, port)
        hello = await reader.readexactly(36)
        cc = os.urandom(32)
        bad = hmac.new(b"not-the-token", b"c" + hello[4:] + cc,
                       hashlib.sha256).digest()
        writer.write(cc + bad)
        await writer.drain()
        got = await reader.read(64)
        assert got == b""  # dropped
        await server.close()

    _run(scenario())


def test_impostor_server_rejected_by_client(token):
    """Mutual auth: a server that sends a challenge but cannot prove token
    knowledge back (spoofed endpoint after port reuse / TCP hijack) must be
    rejected by the client BEFORE any frame from it is unpickled."""

    async def scenario():
        parsed = []

        async def impostor(reader, writer):
            writer.write(b"RTA\x01" + os.urandom(32))
            await writer.drain()
            try:
                await asyncio.wait_for(reader.readexactly(64), 5.0)
            except Exception:
                pass
            # Wrong proof (impostor has no token), then a malicious frame.
            writer.write(b"\x00" * 32)
            body = pickle.dumps((rpc.KIND_PUSH, None, "evil", {}),
                                protocol=5)
            writer.write(struct.pack("<4sI", rpc._MAGIC, len(body)) + body)
            try:
                await writer.drain()
            except ConnectionError:
                pass

        server = await asyncio.start_server(impostor, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]

        async def on_push(method, data):
            parsed.append(method)

        client = rpc.RpcClient("127.0.0.1", port, on_push=on_push)
        with pytest.raises(rpc.AuthError):
            await client.connect()
        await asyncio.sleep(0.1)
        assert parsed == []
        server.close()

    _run(scenario())


def test_injected_frame_dropped_by_mac(token):
    """A frame whose MAC doesn't verify (TCP injection on an authenticated
    connection) kills the connection without unpickling the body."""

    async def scenario():
        server = rpc.RpcServer()
        handled = []

        async def h(conn, **kw):
            handled.append(kw)
            return {"ok": True}

        server.register("ping", h)
        await server.start()

        client = rpc.RpcClient(*server.address)
        await client.connect()
        assert (await client.call("ping", v=1)) == {"ok": True}

        # Bypass the client's sealing path: write a raw, unMAC'd frame
        # straight onto the socket, as an injector would.
        body = pickle.dumps((rpc.KIND_REQUEST, 99, "ping", {"v": 666}),
                            protocol=5)
        client._writer.write(
            struct.pack("<4sI", rpc._MAGIC, len(body)) + body)
        await client._writer.drain()
        await asyncio.sleep(0.2)
        assert {"v": 666} not in handled  # injected frame never dispatched
        await client.close()
        await server.close()

    _run(scenario())


def test_token_resolved_by_address_with_two_sessions(tmp_path, monkeypatch):
    """Two clusters on one host: session_latest points at the second, but an
    attacher naming the FIRST cluster's address must get the first token."""
    monkeypatch.setenv("RAY_TPU_TMPDIR", str(tmp_path))
    monkeypatch.delenv("RAY_TPU_AUTH_TOKEN", raising=False)
    rpc.set_session_token(None)
    rpc._token_loaded = False

    def mk_session(name, addr, tok):
        d = tmp_path / name
        d.mkdir()
        (d / "gcs_address").write_text(addr)
        (d / "auth_token").write_text(tok)
        return d

    tok_a, tok_b = os.urandom(32).hex(), os.urandom(32).hex()
    mk_session("session_1111_aa", "127.0.0.1:6101", tok_a)
    later = mk_session("session_2222_bb", "127.0.0.1:6202", tok_b)
    (tmp_path / "session_latest").symlink_to(later)

    assert rpc.load_token_for_address("127.0.0.1", 6101)
    assert rpc.get_session_token() == bytes.fromhex(tok_a)

    assert rpc.load_token_for_address("localhost", 6202)
    assert rpc.get_session_token() == bytes.fromhex(tok_b)

    # Unknown address: nothing pinned, caller falls back to session_latest.
    rpc.set_session_token(None)
    rpc._token_loaded = False
    assert not rpc.load_token_for_address("127.0.0.1", 9999)
    assert rpc.get_session_token() == bytes.fromhex(tok_b)
    rpc.set_session_token(None)


def test_frame_mac_rejects_replay():
    mac_a = rpc._FrameMac(b"k" * 32, is_client=True)
    mac_b = rpc._FrameMac(b"k" * 32, is_client=False)
    body = b"hello"
    tag = mac_a.seal(body)
    assert mac_b.verify(body, tag)
    assert not mac_b.verify(body, tag)  # replayed: seq advanced
    # (in production a failed verify kills the connection, so the verifier
    # state after a failure is irrelevant)
    # Reflection: a tag sealed in the server direction never verifies as
    # client traffic, even at matching seq.
    fresh = rpc._FrameMac(b"k" * 32, is_client=False)
    srv = rpc._FrameMac(b"k" * 32, is_client=False)
    assert not fresh.verify(body, srv.seal(body))


def test_correct_token_round_trips(token):
    async def scenario():
        server = rpc.RpcServer()

        async def h(conn, **kw):
            return {"echo": kw["v"]}

        server.register("ping", h)
        await server.start()
        client = rpc.RpcClient(*server.address)
        await client.connect()
        out = await client.call("ping", v=41)
        assert out == {"echo": 41}
        await client.close()
        await server.close()

    _run(scenario())


def test_cluster_mints_token_and_works(tmp_path, monkeypatch):
    """ray_tpu.init mints a session token; the whole control plane
    authenticates (GCS, raylet, workers) and tasks still run."""
    import ray_tpu

    monkeypatch.delenv("RAY_TPU_AUTH_TOKEN", raising=False)
    rpc.set_session_token(None)
    rpc._token_loaded = False
    ray_tpu.init(num_cpus=1)
    try:
        tok = os.environ.get("RAY_TPU_AUTH_TOKEN")
        assert tok and len(tok) == 64
        from ray_tpu.core.worker import global_worker

        session_dir = global_worker().session_dir
        path = os.path.join(session_dir, "auth_token")
        assert open(path).read() == tok
        assert os.stat(path).st_mode & 0o777 == 0o600

        @ray_tpu.remote
        def f(x):
            return x + 1

        assert ray_tpu.get(f.remote(1), timeout=60) == 2

        # A tokenless foreign socket can't get past the raylet handshake.
        core = global_worker()
        host, port = core.raylet.host, core.raylet.port
        s = socket.create_connection((host, port), timeout=5)
        hello = s.recv(36)
        assert hello[:3] == b"RTA"
        s.sendall(b"\x00" * 64)  # cc + garbage proof
        s.settimeout(5)
        assert s.recv(64) == b""  # dropped
        s.close()
    finally:
        ray_tpu.shutdown()
