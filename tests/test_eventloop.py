"""Event-loop serving dataplane (utils/eventloop.py) tier-1 suite.

Pins the ISSUE-15 contracts: keep-alive reuse and pipelining on one
socket, batched GET/PUT over both fronts, needle-cache admission +
invalidation on write/delete/vacuum, a slow client not stalling the
loop (partial-write readiness), shed/deadline/trace/reqlog behavior
unchanged through the reactor's dispatch path, and stop() under open
keep-alive connections returning inside a bounded deadline.
"""

from __future__ import annotations

import json
import os
import socket
import struct
import threading
import time

import pytest

# the whole module exercises the reactor dataplane; a run that forced
# the thread-per-connection fallback has nothing to test here
pytestmark = pytest.mark.skipif(
    os.environ.get("WEED_DATAPLANE") == "threaded",
    reason="reactor dataplane disabled by WEED_DATAPLANE=threaded")

from seaweedfs_tpu.master.server import MasterServer
from seaweedfs_tpu.utils.httpd import (Response, Router, http_bytes,
                                       http_json, serve, stop_server)
from seaweedfs_tpu.volume_server.server import VolumeServer
from seaweedfs_tpu.volume_server.tcp import TcpVolumeClient, tcp_address
from tests.conftest import free_port


def _recv_one_response(sock) -> tuple[bytes, bytes]:
    """One HTTP response (head, body) framed by Content-Length."""
    buf = b""
    while b"\r\n\r\n" not in buf:
        piece = sock.recv(65536)
        if not piece:
            return buf, b""
        buf += piece
    head, _, rest = buf.partition(b"\r\n\r\n")
    clen = 0
    for line in head.split(b"\r\n")[1:]:
        k, _, v = line.partition(b":")
        if k.strip().lower() == b"content-length":
            clen = int(v.strip())
    while len(rest) < clen:
        piece = sock.recv(65536)
        if not piece:
            break
        rest += piece
    return head, rest[:clen]


@pytest.fixture
def plain_server():
    r = Router("t")

    @r.route("GET", "/ping")
    def ping(req):
        return Response({"ok": True})

    @r.route("POST", "/echo")
    def echo(req):
        return Response(raw=req.body)

    @r.route("GET", "/big")
    def big(req):
        return Response(raw=b"Z" * (4 << 20))

    srv = serve(r, "127.0.0.1", 0)
    yield srv, srv.server_address[1], r
    try:
        stop_server(srv)
    except Exception:
        pass


@pytest.fixture
def pair(tmp_path):
    master = MasterServer(port=free_port(), pulse_seconds=0.3).start()
    d = tmp_path / "v"
    d.mkdir()
    vs = VolumeServer([str(d)], master.url, port=free_port(),
                      pulse_seconds=0.3).start()
    deadline = time.time() + 5
    while time.time() < deadline and not master.topo.all_nodes():
        time.sleep(0.05)
    yield master, vs
    vs.stop()
    master.stop()


def _assign_and_write(master, payload: bytes) -> tuple[str, str]:
    r = http_json("GET", f"http://{master.url}/dir/assign?count=1",
                  timeout=10.0)
    st, _b, _h = http_bytes("POST", f"http://{r['url']}/{r['fid']}",
                            payload, timeout=10.0)
    assert st in (200, 201)
    return r["fid"], r["url"]


# --- keep-alive + pipelining -------------------------------------------------

def test_reactor_is_the_default_server(plain_server):
    srv, _port, _r = plain_server
    assert type(srv).__name__ == "ReactorHTTPServer"


def test_keepalive_many_requests_one_socket(plain_server):
    _srv, port, _r = plain_server
    with socket.create_connection(("127.0.0.1", port), timeout=5) as s:
        for _ in range(20):
            s.sendall(b"GET /ping HTTP/1.1\r\nHost: h\r\n\r\n")
            head, body = _recv_one_response(s)
            assert b" 200 " in head.split(b"\r\n")[0]
            assert b"true" in body


def test_pipelined_requests_answered_in_order(plain_server):
    _srv, port, _r = plain_server
    with socket.create_connection(("127.0.0.1", port), timeout=5) as s:
        # three requests in ONE write; three responses, in order, with
        # distinguishable bodies
        reqs = b""
        for i in range(3):
            body = b"req%d" % i
            reqs += (b"POST /echo HTTP/1.1\r\nHost: h\r\n"
                     b"Content-Length: %d\r\n\r\n%s" % (len(body), body))
        s.sendall(reqs)
        for i in range(3):
            head, body = _recv_one_response(s)
            assert b" 200 " in head.split(b"\r\n")[0]
            assert body == b"req%d" % i


def test_negative_content_length_answers_400(plain_server):
    """A negative Content-Length must be rejected, not parsed into the
    awaiting-headers sentinel (which would orphan the request and
    desync the connection)."""
    _srv, port, _r = plain_server
    with socket.create_connection(("127.0.0.1", port), timeout=5) as s:
        s.sendall(b"GET /ping HTTP/1.1\r\nHost: h\r\n"
                  b"Content-Length: -1\r\n\r\n")
        head, _body = _recv_one_response(s)
        assert b" 400 " in head.split(b"\r\n")[0]


def test_http10_and_connection_close_semantics(plain_server):
    _srv, port, _r = plain_server
    with socket.create_connection(("127.0.0.1", port), timeout=5) as s:
        s.sendall(b"GET /ping HTTP/1.0\r\nHost: h\r\n\r\n")
        head, body = _recv_one_response(s)
        assert b"true" in body
        # HTTP/1.0 without keep-alive: the server closes
        assert s.recv(4096) == b""


def test_stop_with_open_keepalive_connections_is_bounded(plain_server):
    srv, port, _r = plain_server
    conns = [socket.create_connection(("127.0.0.1", port), timeout=5)
             for _ in range(8)]
    for c in conns:  # each completed one request, then idles keep-alive
        c.sendall(b"GET /ping HTTP/1.1\r\nHost: h\r\n\r\n")
        _recv_one_response(c)
    t0 = time.monotonic()
    stop_server(srv)
    took = time.monotonic() - t0
    assert took < 2.0, f"stop under open keep-alive took {took:.2f}s"
    for c in conns:
        c.close()
    # the port is actually released: a fresh bind succeeds immediately
    s = socket.socket()
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    s.bind(("127.0.0.1", port))
    s.close()


def test_slow_client_does_not_stall_the_loop(plain_server):
    """A client that requests 4MB and reads nothing must not block
    other connections: the response parks in the outbox under
    partial-write readiness while fresh requests keep serving."""
    _srv, port, _r = plain_server
    slow = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    # tiny receive buffer (set BEFORE connect so it takes) so the
    # kernel backpressures immediately
    slow.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
    slow.settimeout(10)
    slow.connect(("127.0.0.1", port))
    slow.sendall(b"GET /big HTTP/1.1\r\nHost: h\r\n\r\n")
    time.sleep(0.3)  # response is now wedged against the full socket
    lat = []
    for _ in range(5):
        t0 = time.monotonic()
        st, body, _h = http_bytes("GET", f"http://127.0.0.1:{port}/ping",
                                  timeout=5.0)
        lat.append(time.monotonic() - t0)
        assert st == 200
    assert max(lat) < 1.0, f"loop stalled behind slow client: {lat}"
    # the slow client still gets its full body eventually
    total = 0
    deadline = time.time() + 20
    while total < (4 << 20) and time.time() < deadline:
        piece = slow.recv(65536)
        if not piece:
            break
        total += len(piece)
    assert total >= (4 << 20)
    slow.close()


def test_empty_body_response_does_not_wedge_the_connection(plain_server):
    """302/204-style responses write a zero-length body; an empty item
    reaching the outbox used to spin the flusher forever (sendmsg of
    an all-empty batch reports 0 sent — indistinguishable from no
    progress) and wedge every later flush on the connection."""
    _srv, port, r = plain_server

    @r.route("GET", "/redir")
    def redir(req):
        return Response(None, status=302, raw=b"",
                        headers={"Location": "http://x/y"})

    with socket.create_connection(("127.0.0.1", port), timeout=5) as s:
        for _ in range(3):
            s.sendall(b"GET /redir HTTP/1.1\r\nHost: h\r\n\r\n")
            head, body = _recv_one_response(s)
            assert b" 302 " in head.split(b"\r\n")[0]
            assert body == b""
        # the SAME connection still serves a normal response after the
        # empty-body ones (the wedge showed up exactly here)
        s.sendall(b"GET /ping HTTP/1.1\r\nHost: h\r\n\r\n")
        head, body = _recv_one_response(s)
        assert b" 200 " in head.split(b"\r\n")[0] and b"true" in body


def test_large_response_streams_to_fast_client(plain_server):
    """A response bigger than the slow-client outbox cap must still
    reach a client that IS reading: enqueue drains the socket as it
    writes (and backpressures the worker), so the cap only fires for
    clients that stopped consuming."""
    from seaweedfs_tpu.utils.eventloop import MAX_OUT_BUFFERED

    _srv, port, r = plain_server
    size = MAX_OUT_BUFFERED + (8 << 20)
    blob = b"Q" * size

    @r.route("GET", "/huge")
    def huge(req):
        return Response(raw=blob)

    st, body, _h = http_bytes("GET", f"http://127.0.0.1:{port}/huge",
                              timeout=120.0)
    assert st == 200 and len(body) == size


# --- chokepoint contracts through the reactor dispatch path ------------------

def test_shed_deadline_trace_reqlog_through_reactor():
    from seaweedfs_tpu.observability import (disable_tracing,
                                             enable_tracing, sample_rate,
                                             set_sample_rate)
    from seaweedfs_tpu.observability.reqlog import get_recorder
    from seaweedfs_tpu.utils.admission import AdmissionController

    r = Router("t")
    release = threading.Event()

    @r.route("GET", "/slowpoke")
    def slowpoke(req):
        release.wait(5.0)
        return Response({"ok": True})

    @r.route("GET", "/1,00000000deadbeef")
    def obj(req):
        return Response(raw=b"x" * 64)

    r.admission = AdmissionController(1, role="t")
    srv = serve(r, "127.0.0.1", 0)
    port = srv.server_address[1]
    enable_tracing()
    prev_rate = sample_rate()
    set_sample_rate(0.0)
    rec = get_recorder()
    rec.start(sample=1.0, reset=True)
    try:
        # occupy the one admission slot
        t = threading.Thread(
            target=lambda: http_bytes(
                "GET", f"http://127.0.0.1:{port}/slowpoke",
                timeout=10.0), daemon=True)
        t.start()
        time.sleep(0.3)
        # 1) admission shed: fast 503 + Retry-After while the slot is
        # held (object routes are not exempt)
        t0 = time.monotonic()
        st, _b, hdrs = http_bytes(
            "GET", f"http://127.0.0.1:{port}/1,00000000deadbeef",
            timeout=5.0)
        assert st == 503 and hdrs.get("Retry-After")
        assert time.monotonic() - t0 < 1.0
        release.set()
        t.join(timeout=10)
        # 2) spent deadline answers 504 before dispatch
        st, body, _h = http_bytes(
            "GET", f"http://127.0.0.1:{port}/1,00000000deadbeef",
            headers={"X-Weed-Deadline": "-0.5"}, timeout=5.0)
        assert st == 504, (st, body)
        # 3) forced trace hands back X-Trace-Id
        st, _b, hdrs = http_bytes(
            "GET", f"http://127.0.0.1:{port}/1,00000000deadbeef",
            headers={"X-Force-Trace": "1"}, timeout=5.0)
        assert st == 200 and hdrs.get("X-Trace-Id")
        # 4) the recorder captured the reads with the right route class
        recs = [rec_.to_dict() for rec_ in rec.snapshot()]
        reads = [d for d in recs if d["route"] == "http_read"]
        assert reads, recs
        assert any(d.get("shed") for d in recs)
    finally:
        rec.stop()
        rec.clear()
        disable_tracing()
        # a later test's spans must not inherit the 0 rate
        set_sample_rate(prev_rate)
        stop_server(srv)


def test_deadline_header_format_matches_plane():
    """The -0.5 literal above must stay a valid spent-budget header."""
    from seaweedfs_tpu.utils import deadline as ddl

    d, prev = ddl.begin_request({"X-Weed-Deadline": "-0.5"})
    try:
        assert d is not None and d.expired()
    finally:
        ddl.end_request(prev)


# --- batched GET/PUT ---------------------------------------------------------

def test_http_batch_read_and_write(pair):
    master, vs = pair
    fids = [_assign_and_write(master, b"n%03d" % i * 256)[0]
            for i in range(8)]
    url = vs.url
    st, body, _h = http_bytes(
        "POST", f"http://{url}/batch/read",
        json.dumps({"fids": fids}).encode(), timeout=10.0)
    assert st == 200
    out, i = [], 0
    while i < len(body):
        ok = body[i:i + 1]
        n = struct.unpack(">I", body[i + 1:i + 5])[0]
        i += 5
        out.append((ok, body[i:i + n]))
        i += n
    assert len(out) == len(fids)
    assert all(ok == b"\x00" and len(data) == 1024 for ok, data in out)
    # batch write: overwrite all of them in one request
    frames = b"".join(
        struct.pack(">H", len(f.encode())) + f.encode()
        + struct.pack(">I", 512) + b"\xbb" * 512 for f in fids)
    st, body, _h = http_bytes("POST", f"http://{url}/batch/write",
                              frames, timeout=10.0)
    assert st == 200
    results = json.loads(body)["results"]
    assert all(row["status"] == 201 for row in results)
    for fid in fids:
        st, data, _h = http_bytes("GET", f"http://{url}/{fid}",
                                  timeout=10.0)
        assert st == 200 and data == b"\xbb" * 512

    # a bad fid inside a batch is a per-slot error, not a 500
    st, body, _h = http_bytes(
        "POST", f"http://{url}/batch/read",
        json.dumps({"fids": [fids[0], "999,00000000ffffffff"]}).encode(),
        timeout=10.0)
    assert st == 200
    assert body[0:1] == b"\x00"


def test_tcp_batch_read_and_write(pair):
    master, vs = pair
    fids = [_assign_and_write(master, b"t%03d" % i * 256)[0]
            for i in range(8)]
    tcp = TcpVolumeClient()
    addr = tcp_address(vs.url)
    res = tcp.batch_read(addr, fids)
    assert len(res) == len(fids)
    assert all(r is not None and len(r) == 1024 for r in res)
    # per-slot failure stays a None, and the connection survives
    res = tcp.batch_read(addr, [fids[0], "999,00000000ffffffff"])
    assert res[0] is not None and res[1] is None
    ok = tcp.batch_write(addr, [(f, b"\xcc" * 256) for f in fids[:4]])
    assert ok == [True] * 4
    res = tcp.batch_read(addr, fids[:4])
    assert all(r == b"\xcc" * 256 for r in res)


# --- needle cache ------------------------------------------------------------

def test_needle_cache_admission_hit_and_write_invalidation(pair):
    master, vs = pair
    cache = vs.store.needle_cache
    fid, url = _assign_and_write(master, b"\xa1" * 2048)
    from seaweedfs_tpu.storage.file_id import FileId

    parsed = FileId.parse(fid)
    key = (parsed.volume_id, parsed.key)
    # first read: admission bar (admit_after=2) keeps it OUT
    assert http_bytes("GET", f"http://{url}/{fid}",
                      timeout=10.0)[0] == 200
    assert not cache.contains(*key)
    # second read admits
    assert http_bytes("GET", f"http://{url}/{fid}",
                      timeout=10.0)[0] == 200
    assert cache.contains(*key)
    # cached read serves the same bytes
    st, data, _h = http_bytes("GET", f"http://{url}/{fid}",
                              timeout=10.0)
    assert st == 200 and data == b"\xa1" * 2048
    # overwrite invalidates: the very next read sees the NEW bytes
    st, _b, _h = http_bytes("POST", f"http://{url}/{fid}",
                            b"\xb2" * 1024, timeout=10.0)
    assert st in (200, 201)
    assert not cache.contains(*key)
    st, data, _h = http_bytes("GET", f"http://{url}/{fid}",
                              timeout=10.0)
    assert st == 200 and data == b"\xb2" * 1024
    # delete invalidates too
    http_bytes("GET", f"http://{url}/{fid}", timeout=10.0)
    assert cache.contains(*key)
    st, _b, _h = http_bytes("DELETE", f"http://{url}/{fid}",
                            timeout=10.0)
    assert st == 200
    assert not cache.contains(*key)
    st, _b, _h = http_bytes("GET", f"http://{url}/{fid}", timeout=10.0)
    assert st == 404


def test_needle_cache_vacuum_invalidation_and_bounds(pair):
    master, vs = pair
    cache = vs.store.needle_cache
    from seaweedfs_tpu.storage.file_id import FileId

    fid, url = _assign_and_write(master, b"\xee" * 1024)
    parsed = FileId.parse(fid)
    for _ in range(2):
        http_bytes("GET", f"http://{url}/{fid}", timeout=10.0)
    assert cache.contains(parsed.volume_id, parsed.key)
    # a churned sibling ON THE SAME VOLUME makes it vacuum-worthy
    # (volume servers accept client-named fids, so pin the vid)
    fid2 = f"{parsed.volume_id},00000000cafebabe"
    st, _b, _h = http_bytes("POST", f"http://{url}/{fid2}",
                            b"\x11" * 4096, timeout=10.0)
    assert st in (200, 201)
    http_bytes("DELETE", f"http://{url}/{fid2}", timeout=10.0)
    st = http_json(
        "GET",
        f"http://{master.url}/vol/vacuum?garbageThreshold=0.0001",
        timeout=30.0)
    assert isinstance(st, dict)
    # vacuum commit dropped the volume's cache entries wholesale
    assert not cache.contains(parsed.volume_id, parsed.key)
    # and the post-vacuum read still serves the right bytes
    st, data, _h = http_bytes("GET", f"http://{url}/{fid}",
                              timeout=10.0)
    assert st == 200 and data == b"\xee" * 1024


def test_needle_cache_byte_bound_and_epoch_race_guard():
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.volume_server.needle_cache import (ENTRY_OVERHEAD,
                                                          NeedleCache)

    cache = NeedleCache(max_bytes=8 * (1024 + ENTRY_OVERHEAD),
                        admit_after=1)
    for i in range(16):
        n = Needle(cookie=1, id=i, data=b"x" * 1024)
        assert cache.offer(1, i, n)
    with cache._lock:
        resident = cache._bytes
    assert resident <= cache.max_bytes
    # oldest entries evicted, newest resident
    assert not cache.contains(1, 0)
    assert cache.contains(1, 15)
    # epoch fence: an offer with a pre-invalidation epoch is refused
    ep = cache.epoch(2)
    cache.invalidate(2, 99, "write")
    stale = Needle(cookie=1, id=99, data=b"old")
    assert not cache.offer(2, 99, stale, epoch=ep)
    assert not cache.contains(2, 99)
    # oversized needles never admit
    big = Needle(cookie=1, id=500,
                 data=b"y" * (cache.max_bytes // 4))
    assert not cache.offer(1, 500, big)


# --- live-cluster replay (workload.replay -against) --------------------------

def test_run_against_replays_recording_onto_live_cluster(pair):
    """record -> export -> fit -> replay AGAINST the same live cluster:
    the before/after proof path for this refactor.  The replayed run
    must pass its checks and deliver its open-loop schedule."""
    master, vs = pair
    from seaweedfs_tpu.observability.reqlog import get_recorder
    from seaweedfs_tpu.scenarios import run_against
    from seaweedfs_tpu.scenarios.replay import (replay_fidelity,
                                                spec_from_recording)

    rec = get_recorder()
    rec.start(sample=1.0, reset=True)
    try:
        fids = [_assign_and_write(master, b"\x42" * 2048)
                for _ in range(12)]
        for _ in range(4):
            for fid, url in fids:
                assert http_bytes("GET", f"http://{url}/{fid}",
                                  timeout=10.0)[0] == 200
    finally:
        rec.stop()
    records = [r.to_dict() for r in rec.snapshot()]
    rec.clear()
    recording = {"format": "seaweedfs-tpu-workload-recording-v1",
                 "records": records}
    spec = spec_from_recording(recording, duration_s=3.0, clients=4)
    result = run_against(spec, master.url)
    assert result["against"] == master.url
    assert result["verdict"] == "pass", result["checks"]
    assert result["total_ops"] > 0
    reads = result["routes"].get("read") or {}
    assert reads.get("error_ratio", 1.0) <= 0.02
    fidelity = replay_fidelity(recording, spec, result=result)
    assert all(c["ok"] for c in fidelity
               if c["check"] != "fidelity_pacing"), fidelity
    # the shell command exposes the mode
    from seaweedfs_tpu.shell.workload_commands import \
        cmd_workload_replay

    assert "-against" in (cmd_workload_replay.__doc__ or "")


def test_loop_fast_path_serves_cache_hits(pair):
    master, vs = pair
    from seaweedfs_tpu.stats import dataplane_metrics

    fid, url = _assign_and_write(master, b"\xf0" * 4096)
    for _ in range(3):  # admit
        http_bytes("GET", f"http://{url}/{fid}", timeout=10.0)
    before = dataplane_metrics().totals()["fast_dispatches"]
    for _ in range(5):
        st, data, _h = http_bytes("GET", f"http://{url}/{fid}",
                                  timeout=10.0)
        assert st == 200 and data == b"\xf0" * 4096
    after = dataplane_metrics().totals()["fast_dispatches"]
    assert after - before >= 5
