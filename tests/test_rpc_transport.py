"""Pipelined gradient transport: batched SEND_VARS/GET_VARS frames,
connection striping, zero-copy scatter-gather serde, and the failure
discipline they must preserve (at-most-once for mutating RPCs,
mixed-version peer compatibility, batch-of-N == N toward the sync-round
barrier)."""
import os
import socket
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core.executor import Executor, Scope
from paddle_tpu.core.program import Program
from paddle_tpu.core.selected_rows import SelectedRows
from paddle_tpu.distributed import serde, transport
from paddle_tpu.distributed.ps_ops import PServerLoop
from paddle_tpu.distributed.transport import (BATCH_BARRIER, ERR, GET_VAR,
                                              GET_VARS, OK, SEND_VAR,
                                              SEND_VARS, RPCClient, RPCServer)


# ---------------------------------------------------------------------------
# serde round-trip property tests
# ---------------------------------------------------------------------------

SERDE_CASES = [
    None,
    np.arange(12, dtype="float32").reshape(3, 4),
    np.arange(24, dtype="float64").reshape(2, 3, 4),
    np.array(3.5, dtype="float32"),                  # 0-d
    np.zeros((0, 5), dtype="int64"),                 # zero-size
    np.zeros((0,), dtype="float32"),
    np.array([True, False, True]),                   # bool
    np.arange(10, dtype="int32"),
    np.arange(10, dtype="uint8"),
    np.arange(20, dtype="float32")[::2],             # non-contiguous stride
    np.arange(24, dtype="float32").reshape(4, 6).T,  # non-contiguous layout
]


def _assert_value_equal(got, want):
    if want is None:
        assert got is None
        return
    if isinstance(want, SelectedRows):
        assert isinstance(got, SelectedRows)
        assert got.height == want.height
        np.testing.assert_array_equal(np.asarray(got.rows),
                                      np.asarray(want.rows))
        np.testing.assert_array_equal(np.asarray(got.values),
                                      np.asarray(want.values))
        return
    got = np.asarray(got)
    assert got.dtype == np.asarray(want).dtype
    assert got.shape == np.asarray(want).shape
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("case", range(len(SERDE_CASES)))
@pytest.mark.parametrize("copy", [True, False])
def test_serde_roundtrip(case, copy):
    value = SERDE_CASES[case]
    data = serde.dumps_value(value)
    _assert_value_equal(serde.loads_value(data, copy=copy), value)
    # the vectored form is byte-identical to the contiguous form
    vec = serde.dumps_value_vec(value)
    assert b"".join(bytes(b) for b in vec) == data
    assert serde.buffers_nbytes(vec) == len(data)


@pytest.mark.parametrize("copy", [True, False])
def test_serde_selected_rows_roundtrip(copy):
    sr = SelectedRows(np.array([1, 3, 7], dtype="int64"),
                      np.arange(12, dtype="float32").reshape(3, 4), 10)
    data = serde.dumps_value(sr)
    _assert_value_equal(serde.loads_value(data, copy=copy), sr)
    empty = SelectedRows(np.zeros((0,), "int64"),
                         np.zeros((0, 4), "float32"), 10)
    _assert_value_equal(
        serde.loads_value(serde.dumps_value(empty), copy=copy), empty)


def test_serde_copy_false_view_aliasing_rules():
    """copy=False values are read-only views that pin the recv buffer;
    copy=True values are writable and independently owned."""
    arr = np.arange(8, dtype="float32")
    data = serde.dumps_value(arr)
    view = serde.loads_value(data, copy=False)
    assert not view.flags.writeable
    assert view.base is not None  # aliases the wire buffer
    with pytest.raises(ValueError):
        view[0] = 99.0
    owned = serde.loads_value(data, copy=True)
    assert owned.flags.writeable
    owned[0] = 99.0  # must not require the buffer afterwards
    np.testing.assert_array_equal(view, arr)


def test_serde_batch_roundtrip_and_order():
    pairs = [
        ("w@BLOCK0", np.arange(6, dtype="float32").reshape(2, 3)),
        ("ids", None),
        ("emb", SelectedRows(np.array([0, 2]), np.ones((2, 4), "float32"),
                             6)),
        ("empty", np.zeros((0, 3), "int64")),
        ("flag", np.array([True])),
    ]
    data = serde.dumps_batch(pairs)
    assert b"".join(bytes(b) for b in serde.dumps_batch_vec(pairs)) == data
    for copy in (True, False):
        out = serde.loads_batch(data, copy=copy)
        assert [n for n, _ in out] == [n for n, _ in pairs]
        for (_, got), (_, want) in zip(out, pairs):
            _assert_value_equal(got, want)


def test_serde_batch_rejects_corrupt_item_length():
    data = bytearray(serde.dumps_batch([("x", np.arange(4, dtype="f4"))]))
    data[4 + 2] ^= 0xFF  # flip a byte of the declared value_len
    with pytest.raises(ValueError, match="corrupt batch"):
        serde.loads_batch(bytes(data))


def test_value_nbytes_weights():
    assert serde.value_nbytes(np.zeros((4, 8), "float32")) == 128
    sr = SelectedRows(np.zeros(2, "int64"), np.zeros((2, 3), "float32"), 9)
    assert serde.value_nbytes(sr) == 16 + 24
    assert serde.value_nbytes(None) == 1


# ---------------------------------------------------------------------------
# loopback transport: batched frames, striping, failure discipline
# ---------------------------------------------------------------------------

class _VarStore:
    """Pserver-shaped loopback service with hooks for failure injection."""

    def __init__(self):
        self.vars = {}
        self.lock = threading.Lock()
        self.frames = []          # (msg_type, n_vars) per mutating frame
        self.drop_next_send = 0   # close the conn instead of replying

    def handle(self, msg_type, tid, name, payload):
        if msg_type in (SEND_VAR, SEND_VARS):
            if self.drop_next_send:
                self.drop_next_send -= 1
                with self.lock:
                    self.frames.append((msg_type, None))  # frame ARRIVED
                return None, b""  # _serve_io drop hook: close, no reply
        if msg_type == SEND_VAR:
            v = serde.loads_value(payload)
            with self.lock:
                self.vars[name] = v
                self.frames.append((msg_type, 1))
            return OK, b""
        if msg_type == SEND_VARS:
            pairs = serde.loads_batch(payload, copy=False)
            with self.lock:
                for n, v in pairs:
                    self.vars[n] = v
                self.frames.append((msg_type, len(pairs)))
            return OK, b""
        if msg_type == GET_VAR:
            with self.lock:
                v = self.vars[name]
            return OK, serde.dumps_value(v)
        if msg_type == GET_VARS:
            names = [n for n, _ in serde.loads_batch(payload)]
            with self.lock:
                pairs = [(n, self.vars[n]) for n in names]
            return OK, serde.dumps_batch_vec(pairs)
        return OK, b""


@pytest.fixture(params=["python", "native"])
def loopback(request):
    backend = request.param
    if backend == "native":
        from paddle_tpu.distributed.transport import _native_lib
        if _native_lib() is None:
            pytest.skip("native transport unavailable")
    fluid.set_flags({"rpc_transport": backend})
    store = _VarStore()
    srv = RPCServer("127.0.0.1:0", store)
    srv.start()
    try:
        yield store, f"127.0.0.1:{srv.port}"
    finally:
        srv.stop()
        fluid.set_flags({"rpc_transport": "native"})


def test_send_get_vars_roundtrip(loopback):
    store, ep = loopback
    client = RPCClient(0)
    big = np.arange(1 << 16, dtype="float32")
    sr = SelectedRows(np.array([1, 4]), np.ones((2, 3), "float32"), 8)
    client.send_vars(ep, [("a", np.arange(5.0)), ("big", big), ("sr", sr)])
    assert store.frames == [(SEND_VARS, 3)]
    vals = client.get_vars(ep, ["big", "a"])
    np.testing.assert_array_equal(vals[0], big)
    np.testing.assert_array_equal(vals[1], np.arange(5.0))
    # legacy per-var messages coexist on the same connection
    client.send_var(ep, "z", np.ones(3))
    np.testing.assert_array_equal(client.get_var(ep, "z"), np.ones(3))


def test_send_vars_empty_is_noop(loopback):
    store, ep = loopback
    client = RPCClient(0)
    client.send_vars(ep, [])
    assert client.get_vars(ep, []) == []
    assert store.frames == []


def test_send_vars_stripe_chunking_preserves_all_vars(loopback):
    """A big batch splits across stripes at VAR granularity: every var
    arrives exactly once, as multiple smaller SEND_VARS frames."""
    store, ep = loopback
    fluid.set_flags({"rpc_stripe_chunk_bytes": 1 << 16,
                     "rpc_conns_per_endpoint": 3})
    try:
        client = RPCClient(0)
        pairs = [(f"p{i}", np.full((64, 64), i, "float32"))
                 for i in range(7)]
        client.send_vars(ep, pairs)
    finally:
        fluid.set_flags({"rpc_stripe_chunk_bytes": 8 << 20,
                         "rpc_conns_per_endpoint": 2})
    assert sorted(store.vars) == sorted(n for n, _ in pairs)
    for n, want in pairs:
        np.testing.assert_array_equal(np.asarray(store.vars[n]), want)
    sent = [c for t, c in store.frames if t == SEND_VARS]
    assert len(sent) > 1 and sum(sent) == 7  # split, nothing duplicated


def test_striped_send_vars_no_deadlock_under_saturated_pool(loopback):
    """Stripe sub-batches must not be resubmitted to the shared fan-out
    pool: with every worker already holding an outer send_vars task
    (>=16 endpoint groups), nested submit+result would deadlock the
    step permanently.  20 concurrent striping sends must complete."""
    store, ep = loopback
    fluid.set_flags({"rpc_stripe_chunk_bytes": 1024,
                     "rpc_conns_per_endpoint": 2})
    try:
        client = RPCClient(0)
        calls = [(client.send_vars, ep,
                  [(f"s{i}_{j}", np.full(512, i, "float32"))
                   for j in range(4)]) for i in range(20)]
        done = []
        t = threading.Thread(target=lambda: done.append(
            client.parallel(calls)), daemon=True)
        t.start()
        t.join(timeout=60)
        assert done, "striped send_vars deadlocked on the shared pool"
    finally:
        fluid.set_flags({"rpc_stripe_chunk_bytes": 8 << 20,
                         "rpc_conns_per_endpoint": 2})
    assert len(store.vars) >= 80  # every var from every call arrived


def test_send_vars_connection_drop_surfaces_error_no_retry(loopback):
    """At-most-once: a connection drop mid-SEND_VARS surfaces the error
    to the caller and the frame is NEVER silently re-sent (the server
    may already have applied it)."""
    store, ep = loopback
    client = RPCClient(0)
    client.send_vars(ep, [("warm", np.zeros(2))])  # connect + sanity
    n_before = len(store.frames)
    store.drop_next_send = 1
    with pytest.raises(ConnectionError):
        client.send_vars(ep, [("x", np.arange(3.0)), ("y", np.ones(2))])
    time.sleep(0.1)  # let the server thread finish the dropped handler
    # exactly ONE frame hit the server for this batch — no second attempt
    assert len(store.frames) == n_before + 1
    assert store.frames[-1] == (SEND_VARS, None)
    # the channel recovers for the next round
    client.send_vars(ep, [("x2", np.arange(3.0))])
    assert ("x2" in store.vars)


def test_get_vars_is_idempotent_and_retries_stale_conn(loopback):
    """GET_VARS is read-only: a stale cached connection (server closed
    it) is transparently retried, unlike SEND_VARS."""
    store, ep = loopback
    client = RPCClient(0)
    fluid.set_flags({"rpc_conns_per_endpoint": 1})
    try:
        client.send_vars(ep, [("v", np.arange(4.0))])
        # kill the client's cached connection from our side so the next
        # request hits a dead socket
        pool = client._conns[ep]
        for c in pool:
            if c is not None:
                c.io.close()
        (val,) = client.get_vars(ep, ["v"])
        np.testing.assert_array_equal(val, np.arange(4.0))
    finally:
        fluid.set_flags({"rpc_conns_per_endpoint": 2})


def test_legacy_send_var_interop_with_batched_server(loopback):
    """Mixed-version peers: a client with batching disabled (the legacy
    wire) trains against a server that also speaks SEND_VARS."""
    store, ep = loopback
    fluid.set_flags({"rpc_batch_vars": 0, "rpc_vectored_io": 0})
    try:
        client = RPCClient(0)
        client.send_var(ep, "legacy", np.arange(6.0))
        np.testing.assert_array_equal(client.get_var(ep, "legacy"),
                                      np.arange(6.0))
        assert store.frames == [(SEND_VAR, 1)]
    finally:
        fluid.set_flags({"rpc_batch_vars": 1, "rpc_vectored_io": 1})


def test_striping_uses_multiple_connections(loopback):
    """With N stripes, concurrent requests to ONE endpoint run on
    distinct connections (no single-conn serialization)."""
    store, ep = loopback
    fluid.set_flags({"rpc_conns_per_endpoint": 3})
    try:
        client = RPCClient(0)
        hold = threading.Event()
        release = threading.Event()

        orig = store.handle

        def slow_handle(msg_type, tid, name, payload):
            if msg_type == GET_VAR and name == "slow":
                hold.set()
                release.wait(timeout=10)
                name = "fast"
            return orig(msg_type, tid, name, payload)

        store.handle = slow_handle
        store.vars["fast"] = np.ones(2)
        t = threading.Thread(
            target=lambda: client.get_var(ep, "slow"), daemon=True)
        t.start()
        assert hold.wait(timeout=10)
        # the slow request holds one stripe; this must not block
        np.testing.assert_array_equal(client.get_var(ep, "fast"),
                                      np.ones(2))
        release.set()
        t.join(timeout=10)
        assert not t.is_alive()
        live = [c for c in client._conns[ep] if c is not None]
        assert len(live) >= 2
    finally:
        fluid.set_flags({"rpc_conns_per_endpoint": 2})


def test_vectored_io_flag_off_same_wire_bytes(loopback):
    """FLAGS_rpc_vectored_io=0 joins buffers before send; the peer sees
    identical frames either way."""
    store, ep = loopback
    client = RPCClient(0)
    payload = np.arange(1024, dtype="float64")
    client.send_vars(ep, [("vec", payload)])
    fluid.set_flags({"rpc_vectored_io": 0})
    try:
        client.send_vars(ep, [("joined", payload)])
    finally:
        fluid.set_flags({"rpc_vectored_io": 1})
    np.testing.assert_array_equal(np.asarray(store.vars["vec"]),
                                  np.asarray(store.vars["joined"]))


# ---------------------------------------------------------------------------
# push_frames: one frame to each of n native connections in one foreign call
# (the decode plane's token fan-out)
# ---------------------------------------------------------------------------

@pytest.fixture
def native_pairs():
    """``make(n, rcvbuf=None)`` -> n (server-side ``_NativeIO``, peer
    socket) pairs over a native listener; the peers are plain sockets, so a
    test reads the wire's own bytes and decides when (and whether) to read."""
    lib = transport._native_lib()
    if lib is None:
        pytest.skip("native transport unavailable")
    lstn = lib.ptq_listener_create(b"127.0.0.1", 0)
    port = lib.ptq_listener_port(lstn)
    made = []

    def make(n, rcvbuf=None):
        out = []
        for _ in range(n):
            peer = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            if rcvbuf:      # before connect: it caps the window offered
                peer.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
            peer.connect(("127.0.0.1", port))
            peer.settimeout(30)
            io = transport._NativeIO(lib.ptq_listener_accept(lstn))
            out.append((io, peer))
        made.extend(out)
        return out

    try:
        yield make
    finally:
        for io, peer in made:
            peer.close()
            io.close()
        lib.ptq_listener_close(lstn)


def _token_frame(tid, name, tokens):
    return transport._pack_body_vec(
        OK, tid, name, [b"T"] + serde.dumps_batch_vec(
            [("tokens", np.asarray(tokens, np.int32))]))


def _read_exact(sock, n):
    got = b""
    while len(got) < n:
        chunk = sock.recv(n - len(got))
        assert chunk, f"peer closed after {len(got)} of {n} bytes"
        got += chunk
    return got


def _read_frames(sock, count):
    out = []
    for _ in range(count):
        (n,) = np.frombuffer(_read_exact(sock, 4), "<u4")
        out.append(_read_exact(sock, int(n)))
    return out


def test_push_frames_writes_the_bytes_send_frame_vec_writes(native_pairs):
    """n frames for n connections in one call: every peer reads, byte for
    byte, what ``send_frame_vec`` puts on the wire for the same buffers,
    and a frame the connection's own thread sends after ``finish_frames``
    follows the pushed ones."""
    n = 8
    pushed, vectored = native_pairs(n), native_pairs(n)
    rounds = [[_token_frame(i, "lm" + "x" * i,
                            [(-1) ** i * (1 << 20) * (i + r) + i])
               for i in range(n)] for r in range(3)]
    for frames in rounds:
        rcs = transport.push_frames([io for io, _ in pushed],
                                    [b"".join(f) for f in frames])
        assert rcs == [transport.PUSHED] * n
        for (io, _), f in zip(vectored, frames):
            io.send_frame_vec(f)
    for io, _ in pushed + vectored:
        io.finish_frames()          # waits for the writer; nothing was cut
        io.send_frame(b"FIN")
    for frames in rounds + [[[b"FIN"]] * n]:
        for (_, got), (_, want), f in zip(pushed, vectored, frames):
            size = 4 + sum(len(b) for b in f)
            wire = _read_exact(want, size)
            assert _read_exact(got, size) == wire
            assert wire[4:] == b"".join(f) and len(wire) < 64


def test_push_frames_a_full_socket_blocks_nobody_and_tears_no_frame(
        native_pairs):
    """A peer that does not read: the call still returns at once (it does no
    I/O), the next call reports "would block" for that connection alone,
    the others' frames go on arriving, and when the peer reads again it
    gets every frame whole and in order — the rest of the one its socket
    cut and those kept behind it, written by ``finish_frames``."""
    (slow_io, slow), = native_pairs(1, rcvbuf=4096)
    others = native_pairs(3)
    ios = [slow_io] + [io for io, _ in others]
    body = bytes(range(256)) * 256                       # 64 KiB a frame
    sent, verdicts = 0, None
    for k in range(4000):
        t0 = time.perf_counter()
        verdicts = transport.push_frames(
            ios, [k.to_bytes(4, "little") + body] * len(ios))
        assert time.perf_counter() - t0 < 1.0
        sent += 1
        # the others' k-th frames are written after the slow one's: once
        # they are read, the next verdict is on the slow one's k-th
        for _, peer in others:
            (frame,) = _read_frames(peer, 1)
            assert frame[:4] == k.to_bytes(4, "little")
        if verdicts[0] != transport.PUSHED:
            break
    assert verdicts == [transport.PUSH_WOULD_BLOCK] + [transport.PUSHED] * 3
    # a connection that remembers something keeps later frames behind it
    for k in range(sent, sent + 3):
        verdicts = transport.push_frames(
            ios, [k.to_bytes(4, "little") + body] * len(ios))
        assert verdicts == [transport.PUSH_WOULD_BLOCK] + \
            [transport.PUSHED] * 3
        for _, peer in others:
            _read_frames(peer, 1)
    sent += 3
    got = []
    reader = threading.Thread(
        target=lambda: got.extend(_read_frames(slow, sent + 1)), daemon=True)
    reader.start()
    slow_io.finish_frames()                              # blocking
    slow_io.send_frame(b"FIN")
    reader.join(timeout=60)
    assert not reader.is_alive() and len(got) == sent + 1
    for k, frame in enumerate(got[:sent]):
        assert frame == k.to_bytes(4, "little") + body
    assert got[sent] == b"FIN"
    # the connection can be pushed to again, from a clean slate
    assert transport.push_frames([slow_io], [b"again"]) == [transport.PUSHED]
    assert _read_frames(slow, 1) == [b"again"]


def test_push_frames_a_dead_peer_is_an_error_for_it_alone(native_pairs):
    pairs = native_pairs(3)
    ios = [io for io, _ in pairs]
    pairs[1][1].close()
    verdicts, pushed = None, 0
    for _ in range(200):      # the reset may take a frame or two to come back
        verdicts = transport.push_frames(ios, [b"frame"] * 3)
        pushed += 1
        if verdicts[1] == transport.PUSH_DEAD:
            break
        time.sleep(0.01)
    assert verdicts == [transport.PUSHED, transport.PUSH_DEAD,
                        transport.PUSHED]
    closed = transport._NativeIO(None)     # a connection already closed
    assert transport.push_frames([ios[0], closed], [b"a", b"b"]) == \
        [transport.PUSHED, transport.PUSH_DEAD]
    with pytest.raises(ConnectionError):
        ios[1].finish_frames()
    assert _read_frames(pairs[0][1], pushed + 1) == \
        [b"frame"] * pushed + [b"a"]


# ---------------------------------------------------------------------------
# PServerLoop: batch-of-N counts as N toward the sync-round barrier
# ---------------------------------------------------------------------------

class _FakeOp:
    def __init__(self, **attrs):
        self._attrs = attrs

    def attr(self, name, default=None):
        return self._attrs.get(name, default)


def _bare_loop(num_trainers=2):
    op = _FakeOp(sync_mode=True, Fanin=num_trainers, grad_to_block={},
                 lr_block=-1, lr_fetch=[], dense_merge="mean",
                 persist_names=[], dist_tables={}, checkpoint_dir=None,
                 checkpoint_every_rounds=0, endpoint="127.0.0.1:0")
    return PServerLoop(Executor(), Program(), op, Scope())


def test_pserver_send_vars_counts_n_toward_barrier():
    """A SEND_VARS batch of N is indistinguishable from N SEND_VARs to
    the batch_barrier accounting: the round closes only when every
    trainer's barrier lands, and each batched var is buffered
    individually."""
    loop = _bare_loop(num_trainers=2)
    batch = serde.dumps_batch([("g0", np.ones(2)), ("g1", np.zeros(3)),
                               ("g2", np.full(4, 7.0))])
    assert loop.handle(SEND_VARS, 0, "", batch) == (OK, b"")
    assert set(loop.open_round[0]) == {"g0", "g1", "g2"}
    assert loop.applied_rounds == 0

    # trainer 0 closes its round; trainer 1 still pending -> not applied
    loop.handle(BATCH_BARRIER, 0, "", b"")
    assert loop.applied_rounds == 0 and loop.rounds_sent[0] == 1

    # trainer 1 sends the same vars legacy-style (mixed-version peer)
    for n, v in (("g0", np.ones(2)), ("g1", np.zeros(3)),
                 ("g2", np.full(4, 7.0))):
        loop.handle(SEND_VAR, 1, n, serde.dumps_value(v))
    assert set(loop.open_round[1]) == {"g0", "g1", "g2"}
    loop.handle(BATCH_BARRIER, 1, "", b"")
    assert loop.applied_rounds == 1  # both trainers in -> round applied

    # GET_VARS answers post-barrier values as one batch, in order
    loop.scope.set_var("g0", np.ones(2))
    loop.scope.set_var("g1", np.zeros(3))
    rtype, rpayload = loop.handle(GET_VARS, 0, "",
                                  serde.dumps_batch([("g1", None),
                                                     ("g0", None)]))
    assert rtype == OK
    out = serde.loads_batch(b"".join(bytes(b) for b in rpayload)
                            if isinstance(rpayload, list) else rpayload)
    assert [n for n, _ in out] == ["g1", "g0"]
    np.testing.assert_array_equal(out[0][1], np.zeros(3))


def test_pserver_get_vars_unknown_name_errors():
    loop = _bare_loop(num_trainers=1)
    loop.sync_mode = False
    with pytest.raises(KeyError):
        loop.handle(GET_VARS, 0, "", serde.dumps_batch([("nope", None)]))


# ---------------------------------------------------------------------------
# wait_server_ready: host normalization + probe fallback (ADVICE r5)
# ---------------------------------------------------------------------------

def test_wait_server_ready_normalizes_ready_file_host(tmp_path):
    """A server that announced under a different host spelling
    (0.0.0.0 / localhost) still satisfies a 127.0.0.1 waiter.  The
    wildcard spelling names no host (on a shared ready-dir it could be
    another machine's same-port server), so it is only accepted once a
    connect probe confirms a live local listener."""
    (tmp_path / "localhost:7202.ready").write_text("x")
    fluid.distributed.wait_server_ready(["127.0.0.1:7202"], timeout=2,
                                        ready_dir=str(tmp_path))

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    s.listen(1)
    try:
        port = s.getsockname()[1]
        (tmp_path / f"0.0.0.0:{port}.ready").write_text("x")
        fluid.distributed.wait_server_ready([f"127.0.0.1:{port}"],
                                            timeout=5,
                                            ready_dir=str(tmp_path))
    finally:
        s.close()
    # wildcard file WITHOUT a live listener is not trusted (the socket
    # above is closed, so its ephemeral port is guaranteed dead)
    (tmp_path / f"0.0.0.0:{port}.ready").write_text("x")
    with pytest.raises(TimeoutError):
        fluid.distributed.wait_server_ready([f"127.0.0.1:{port}"],
                                            timeout=1.0,
                                            ready_dir=str(tmp_path),
                                            probe_grace=5.0)


def test_wait_server_ready_probe_fallback_after_grace(tmp_path):
    """With PADDLE_READY_DIR set but no ready-file ever appearing, a
    LIVE listener is accepted via the connect-probe fallback once the
    grace period expires (previously: guaranteed timeout)."""
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    s.listen(1)
    try:
        ep = f"127.0.0.1:{s.getsockname()[1]}"
        t0 = time.monotonic()
        fluid.distributed.wait_server_ready([ep], timeout=30,
                                            ready_dir=str(tmp_path),
                                            probe_grace=0.2)
        assert time.monotonic() - t0 < 20
    finally:
        s.close()


def test_wait_server_ready_still_times_out_when_dead(tmp_path):
    with pytest.raises(TimeoutError):
        fluid.distributed.wait_server_ready(
            ["127.0.0.1:45679"], timeout=1.0, ready_dir=str(tmp_path),
            probe_grace=0.1)
