"""Tiny threaded HTTP server framework + JSON client helpers.

The control-plane transport for this rebuild: the reference exposes HTTP for
object IO and /dir/* master endpoints (weed/server/*_handlers*.go) plus gRPC
for admin; here the admin RPCs are HTTP POST endpoints named after their
reference RPCs (a protobuf/gRPC transport can slot in behind the same
handler functions later).
"""

from __future__ import annotations

import json
import os
import re
import threading
import time as _time
import urllib.error
import urllib.parse
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Optional

from ..observability import context as _trace_context
from ..observability import get_tracer as _get_tracer
from ..observability import reqlog as _reqlog
from ..observability.tracer import NOOP_SPAN as _NOOP_SPAN
from . import deadline as _deadline
from .deadline import DeadlineExceeded

# the process-global workload recorder (observability/reqlog.py): the
# dispatch chokepoint reads ONE attribute per request while recording
# is off
_RECORDER = _reqlog.get_recorder()


class HttpError(Exception):
    def __init__(self, status: int, message: str = "",
                 headers: Optional[dict] = None):
        super().__init__(message or f"HTTP {status}")
        self.status = status
        self.message = message
        self.headers = headers or {}


def qint(query: dict, name: str, default: Optional[int] = None) -> int:
    """Parse an int query param, answering 400 (not 500) to garbage —
    a typo'd ?limit=abc is the CLIENT's mistake and must not burn the
    error-ratio SLO budget.  With no `default` the parameter is
    REQUIRED: absence answers 400 too, never a silent zero.  The
    weedlint W601 rule enforces that every route handler parses params
    this way (or with its own try/ValueError -> HttpError(400))."""
    raw = query.get(name)
    if raw is None or raw == "":
        if default is None:
            raise HttpError(400, f"missing query parameter {name}")
        return default
    try:
        return int(raw)
    except (TypeError, ValueError):
        raise HttpError(400, f"bad query parameter {name}={raw!r}: "
                             f"expected an integer")


def qfloat(query: dict, name: str,
           default: Optional[float] = None) -> float:
    """Float twin of qint: malformed or missing-required input answers
    400, never 500."""
    raw = query.get(name)
    if raw is None or raw == "":
        if default is None:
            raise HttpError(400, f"missing query parameter {name}")
        return default
    try:
        return float(raw)
    except (TypeError, ValueError):
        raise HttpError(400, f"bad query parameter {name}={raw!r}: "
                             f"expected a number")


class Request:
    def __init__(self, handler: BaseHTTPRequestHandler, match: re.Match):
        self.handler = handler
        self.match = match
        parsed = urllib.parse.urlparse(handler.path)
        # %-escapes are decoded HERE, once, like Go's r.URL.Path (the
        # reference handlers all consume the decoded form); handlers and
        # route regexes see real names, clients re-quote when building URLs.
        # raw_path keeps the wire form for signature canonicalization
        # (SigV4 must see what the client signed, like Go's URL.RawPath)
        self.raw_path = parsed.path
        self.path = urllib.parse.unquote(parsed.path)
        self.query = {k: v[0] for k, v in urllib.parse.parse_qs(
            parsed.query, keep_blank_values=True).items()}
        self.headers = handler.headers
        self._body: Optional[bytes] = None

    @property
    def body(self) -> bytes:
        if self._body is None:
            length = int(self.headers.get("Content-Length") or 0)
            self._body = self.handler.rfile.read(length) if length else b""
        return self._body

    def json(self) -> dict:
        return json.loads(self.body or b"{}")


def parse_form_data(body: bytes, content_type: str) -> dict:
    """Minimal multipart/form-data parser for POST uploads: returns
    {field: str} plus {"file": bytes, "file.name": str} for the file
    part.  Per the S3 POST contract, fields after `file` are ignored."""
    m = re.search(r'boundary="?([^";]+)"?', content_type)
    if not m:
        raise ValueError("no multipart boundary")
    # RFC 2046 delimiters are CRLF--boundary, NOT the bare boundary
    # bytes — a file whose CONTENT contains the boundary string must
    # survive.  Prefixing CRLF makes the first (dashless) delimiter
    # uniform with the rest.
    sep = b"\r\n--" + m.group(1).encode()
    fields: dict = {}
    for part in (b"\r\n" + body).split(sep)[1:]:
        if part.startswith(b"--"):
            break  # closing delimiter
        part = part.lstrip(b" \t")  # transport padding after boundary
        if part.startswith(b"\r\n"):
            part = part[2:]
        head, hsep, payload = part.partition(b"\r\n\r\n")
        if not hsep and not head.strip():
            continue
        disp = ""
        ptype = ""
        for line in head.split(b"\r\n"):
            low = line.lower()
            if low.startswith(b"content-disposition:"):
                disp = line.decode(errors="replace")
            elif low.startswith(b"content-type:"):
                ptype = line.split(b":", 1)[1].strip().decode(errors="replace")
        nm = re.search(r'name="([^"]*)"', disp)
        name = nm.group(1) if nm else ""
        if name.lower() == "file":
            fn = re.search(r'filename="([^"]*)"', disp)
            fields["file"] = payload
            fields["file.name"] = fn.group(1) if fn else ""
            if ptype:
                fields.setdefault("content-type", ptype)
            break  # everything after the file part is ignored
        fields[name.lower()] = payload.decode(errors="replace")
    return fields


def extract_upload(body: bytes, content_type: str) -> tuple[bytes, str, str]:
    """-> (data, filename, mime) for a write-request body: unwraps one
    multipart/form-data file part the way the reference's needle
    ParseUpload does (needle_parse_upload.go:37-76); raw bodies pass
    through with the request Content-Type as the mime."""
    if content_type and content_type.lower().startswith("multipart/form-data"):
        try:
            fields = parse_form_data(body, content_type)
        except ValueError as e:
            raise HttpError(400, str(e))  # client framing error, not a 500
        if "file" in fields:
            # basename only (needle_parse_upload.go:141 path.Base): a
            # crafted filename must not escape the target directory
            fname = fields.get("file.name", "")
            fname = fname.replace("\\", "/").rsplit("/", 1)[-1]
            return fields["file"], fname, fields.get("content-type", "")
    return body, "", content_type


class Response:
    def __init__(self, data=None, status: int = 200, raw: Optional[bytes] = None,
                 headers: Optional[dict] = None,
                 file_path: Optional[str] = None,
                 file_range: Optional[tuple[int, int]] = None):
        self.data = data
        self.status = status
        self.raw = raw
        # file streaming: the body is (a range of) a file on disk, sent in
        # bounded chunks — a 30GB .dat copy never materializes in memory
        # (the streaming VolumeEcShardRead / CopyFile analog)
        self.file_path = file_path
        self.file_range = file_range  # (offset, length) or None = whole file
        self.headers = headers or {}


class Router:
    """Method+regex route table shared by master/volume/filer servers.

    When `metrics` is set (a stats._ServerMetrics bundle), every dispatch
    increments the request counter and observes latency, labeled by handler
    name — the per-operation labeling of stats/metrics.go collectors."""

    def __init__(self, name: str = "httpd", metrics=None):
        self.name = name
        self.metrics = metrics
        self.routes: list[tuple[str, re.Pattern, Callable]] = []
        # optional exception -> Response mapper, consulted before the
        # default JSON error mapping (the S3 gateway uses it to emit
        # protocol-correct XML errors)
        self.error_handler: Optional[Callable[[Exception], Optional[Response]]] = None
        # optional admission controller (utils/admission.py): servers
        # started with -maxInflight > 0 install one; None costs a
        # single attribute check per request
        self.admission = None
        # optional loop fast-path probe (utils/eventloop.py): when set,
        # the reactor asks `probe(method, path) -> bool` whether a
        # GET/HEAD can dispatch INLINE on the event loop (the volume
        # server answers True only for needle-cache-resident objects).
        # None = every request dispatches on the worker pool.
        self.loop_fast_probe = None
        # optional heat accumulator (observability/heat.py): the volume
        # server installs its per-server HeatAccumulator so every
        # object-route response feeds decayed per-volume/per-needle
        # heat.  None costs a single attribute check per request.
        self.heat = None
        # optional resource ledger (observability/ledger.py): servers
        # install their per-server RequestLedger so every dispatched
        # request settles its thread-CPU / bytes / queue-wait into the
        # per-route and per-client cost tables.  None costs a single
        # attribute check per request.
        self.ledger = None
        # deadline_exceeded journal rate limit (the counter counts every
        # 504; the ring must not churn under a deadline storm).  A lost
        # write race costs at most one extra journal event.
        self._last_ddl_event = 0.0

    def _note_deadline_exceeded(self) -> None:
        """Count + journal (rate-limited) one budget-spent 504."""
        from ..stats import request_plane_metrics

        request_plane_metrics().deadline_exceeded.inc(self.name)
        now = _time.monotonic()
        if now - self._last_ddl_event >= 1.0:
            self._last_ddl_event = now
            from ..observability import events as _events

            _events.emit("deadline_exceeded", role=self.name)

    def route(self, method: str, pattern: str):
        compiled = re.compile("^" + pattern + "$")

        def deco(fn):
            self.routes.append((method, compiled, fn))
            if self.metrics is not None:
                # pre-touch each handler's series at registration so
                # /metrics exposes zero-valued counters and full (+Inf/
                # _sum/_count) histograms before first traffic — absent
                # series break rate() dashboards and alerts
                self.metrics.request_counter.labels(fn.__name__)
                self.metrics.request_histogram.labels(fn.__name__)
                errs = getattr(self.metrics, "request_errors", None)
                if errs is not None:
                    errs.labels(fn.__name__)
            return fn

        return deco

    def dispatch(self, handler: BaseHTTPRequestHandler, method: str) -> None:
        if getattr(handler.server, "_stopping", False):
            # a stopped server's keep-alive connections outlive
            # server_close(); without this, a client pinned to such a
            # connection keeps talking to the ZOMBIE server object while a
            # fresh server owns the port (master-restart convergence bug)
            handler.close_connection = True
            self._send(handler, Response({"error": "server shutting down"},
                                         status=503,
                                         headers={"Connection": "close"}))
            return
        path = urllib.parse.unquote(urllib.parse.urlparse(handler.path).path)
        # resource-ledger entry stamp (observability/ledger.py): minted
        # ON the executing thread — thread-CPU clocks are per-thread,
        # so the reactor's worker handoff needs the stamp here, not at
        # parse time (queue wait rides separately in
        # handler.queue_wait_s, stamped by the reactor at handoff)
        ledger = self.ledger
        ltok = ledger.begin() if ledger is not None else None
        # distributed-trace ingress (observability/context.py): adopt the
        # caller's Traceparent (or make a fresh head-based sampling
        # decision) for the duration of this request, restoring the
        # thread-local afterwards — handler threads are pooled per
        # connection, and a leaked context would bleed into the next
        # request.  Gated on tracer.enabled so the dormant hot-path cost
        # stays one attribute check; with tracing on, an unsampled
        # request pays one header parse + one random() and every span
        # call below degrades to the shared no-op.
        tracer = _get_tracer()
        tctx = _prev_ctx = _prev_srv = None
        traced = False
        if tracer.enabled:
            tctx, _prev_ctx = _trace_context.begin_request(handler.headers)
            traced = True
            # stamp this thread with the OWNING server's identity so
            # spans attribute per-server even when several servers share
            # one process tracer (`weed server`, in-process fixtures);
            # servers set router.server_url to their advertised url, the
            # Host header stands in for routers that never did
            _prev_srv = _trace_context.swap_server(
                getattr(self, "server_url", None)
                or handler.headers.get("Host"))
        # deadline ingress (utils/deadline.py): adopt the caller's
        # X-Weed-Deadline (re-anchored to the local monotonic clock)
        # for the duration of this request, restored in the finally —
        # the same pooled-thread hygiene as the trace context.  Costs
        # one header get when absent.
        ddl, _prev_ddl = _deadline.begin_request(handler.headers)
        try:
            for m, pattern, fn in self.routes:
                if m != method:
                    continue
                match = pattern.match(path)
                if not match:
                    continue
                t0 = _time.perf_counter()
                req = Request(handler, match)
                admission = self.admission
                admitted = False
                shed = False
                # request span: the path carries the needle/volume id for
                # object routes (/<vid>,<fid>), so a trace timeline can be
                # joined back to specific keys.  The span re-roots under
                # the caller's span id (the trace context's parent), which
                # is the edge the master-side collector stitches on.
                # gate on the SAMPLED context, not just tracer.enabled:
                # at 1% sampling the other 99% of requests skip even the
                # span-name f-string and attrs dict
                try:
                    if ddl is not None and ddl.expired():
                        # the caller's budget is already spent: a
                        # 504-style answer NOW beats doing work nobody
                        # will read — and the moment is counted +
                        # journaled, so budget exhaustion pages instead
                        # of hiding inside generic timeouts
                        self._note_deadline_exceeded()
                        resp = Response(
                            {"error": "deadline exceeded before "
                                      "dispatch"}, status=504)
                    elif admission is not None \
                            and not admission.exempt(path) \
                            and not admission.try_acquire():
                        # over the inflight bound: shed with a fast 503
                        # + Retry-After instead of queueing into a late
                        # timeout (try_acquire counted + journaled it).
                        # Close the connection so the unread body is
                        # the accept loop's bounded-drain problem, not
                        # a keep-alive desync.
                        handler.close_connection = True
                        shed = True
                        resp = Response(
                            {"error": "overloaded: request shed"},
                            status=503,
                            headers={"Retry-After": "1",
                                     "Connection": "close"})
                    else:
                        admitted = admission is not None \
                            and not admission.exempt(path)
                        if tctx is not None:
                            with tracer.span(f"http.{self.name}.{fn.__name__}",
                                             method=method, path=path) as sp:
                                qt0 = getattr(handler, "queue_t0", None)
                                if qt0 is not None:
                                    # the reactor's pool handoff, on the
                                    # tracer's clock: it ends before the
                                    # request span starts, and is kept
                                    # under it so a stitched trace stays
                                    # one tree
                                    tracer.add_span(
                                        "http.queue_wait", qt0,
                                        qt0 + handler.queue_wait_s,
                                        parent_id=sp.span_id, path=path)
                                resp = fn(req)
                        else:
                            resp = fn(req)
                except Exception as e:  # noqa: BLE001 — server must not die
                    resp = None
                    if self.error_handler is not None:
                        try:
                            resp = self.error_handler(e)
                        except Exception:
                            resp = None
                    if resp is None:
                        if isinstance(e, DeadlineExceeded):
                            # the budget ran out DURING the handler
                            # (usually at a downstream egress whose
                            # clamp fired): 504, same accounting as the
                            # pre-dispatch check
                            self._note_deadline_exceeded()
                            resp = Response(
                                {"error": str(e) or "deadline exceeded"},
                                status=504)
                        elif isinstance(e, HttpError):
                            # http_bytes signals an UNREACHABLE peer as
                            # synthetic status 0; a handler re-raising
                            # it must answer 502, not emit an invalid
                            # "HTTP/1.1 0" status line — clients parse
                            # sub-200 as an interim response and hang
                            # waiting for the real one (found by the
                            # scenario engine's partition drill)
                            resp = Response({"error": e.message or str(e)},
                                            status=e.status
                                            if e.status >= 100 else 502,
                                            headers=e.headers or None)
                        elif isinstance(e, (KeyError, LookupError)):
                            resp = Response({"error": str(e)}, status=404)
                        else:
                            resp = Response(
                                {"error": f"{type(e).__name__}: {e}"}, status=500)
                try:
                    if self.metrics is not None:
                        self.metrics.request_counter.inc(fn.__name__)
                        if resp.status >= 500:
                            # per-route 5xx counter: the burn-rate SLO's
                            # numerator (guarded: custom metrics bundles
                            # may predate the family)
                            errs = getattr(self.metrics, "request_errors",
                                           None)
                            if errs is not None:
                                errs.inc(fn.__name__)
                        # RED histogram keyed by route; sampled requests
                        # attach their trace id as an exemplar, so a latency
                        # outlier on /metrics links straight to the stitched
                        # trace that explains it
                        self.metrics.request_histogram.observe(
                            fn.__name__, _time.perf_counter() - t0,
                            exemplar=tctx.trace_id if tctx is not None
                            else None)
                    if tctx is not None:
                        # hand the trace id back so callers (bench, tests,
                        # curl -v) can fetch the stitched cluster trace
                        resp.headers.setdefault("X-Trace-Id", tctx.trace_id)
                    # drain any unread request body first: responding while
                    # the client is still mid-upload resets the connection
                    # and the client never sees the (often 4xx) status.
                    # Discard in bounded chunks — never buffer a rejected
                    # upload.  ONLY a shed skips this (it already marked
                    # the connection closing; the accept loop's bounded
                    # pre-close drain protects the 503) — shedding must
                    # stay a microseconds-fast "no", but an ordinary
                    # Connection: close client's rejected upload still
                    # needs the full drain or the close RSTs its error
                    # response away.
                    if req._body is None and not shed:
                        self._drain_body(handler)
                        req._body = b""
                    self._send(handler, resp)
                    if _RECORDER.enabled:
                        # workload flight recorder (observability/
                        # reqlog.py): one sampled access record per
                        # dispatched request, redacted BEFORE it can
                        # reach the ring.  Sits after _send so the
                        # duration covers the transmission (for
                        # streamed reads the send IS the work).
                        self._record_access(handler, method, fn.__name__,
                                            req, resp, shed, ddl, t0)
                    heat = self.heat
                    if heat is not None:
                        # heat accounting (observability/heat.py): the
                        # fid regex inside note_http gates before any
                        # locking, so control-plane routes cost one
                        # attribute check + one failed regex match
                        try:
                            heat.note_http(
                                method, path, resp.status,
                                self._resp_bytes(resp),
                                tctx.trace_id if tctx is not None
                                else "")
                        except Exception:
                            pass  # accounting never breaks serving
                    if ledger is not None:
                        # resource ledger settle (observability/
                        # ledger.py): CPU delta + bytes + queue wait
                        # into the route/client cost tables.  Sits
                        # after _send like the recorder, so on-loop
                        # fast-path stalls measure the whole hold.
                        try:
                            ledger.settle_http(
                                ltok, method, path, fn.__name__,
                                resp.status, len(req._body or b""),
                                self._resp_bytes(resp),
                                handler.client_address[0]
                                if handler.client_address else "",
                                tctx.trace_id if tctx is not None
                                else "",
                                getattr(handler, "queue_wait_s", 0.0),
                                query=req.query)
                        except Exception:
                            pass  # accounting never breaks serving
                finally:
                    # release only after the RESPONSE left: for large
                    # streamed reads (Response(file_path=...)) the send
                    # IS the work — releasing at handler return would
                    # let unbounded concurrent transmissions pile up
                    # behind an "empty" admission gate
                    if admitted:
                        admission.release()
                return
            # 404 fallthrough: the body was never read, so drain it too or
            # the keep-alive loop would parse the leftover bytes as the next
            # request line (request-smuggling-shaped desync).
            self._drain_body(handler)
            self._send(handler, Response(
                {"error": f"no route {method} {path}"}, status=404))
        finally:
            _deadline.end_request(_prev_ddl)
            if traced:
                _trace_context.end_request(_prev_ctx)
                _trace_context.swap_server(_prev_srv)

    @staticmethod
    def _resp_bytes(resp: Response) -> int:
        """Cheap out-byte estimate for heat accounting — attribute
        checks only, never a syscall (reqlog's getsize fallback is too
        expensive for every response): an unranged streamed file reads
        as 0, so byte rates are a floor, not an exact meter."""
        if resp.raw is not None:
            return len(resp.raw)
        if resp.file_range is not None:
            _off, length = resp.file_range
            return length if length >= 0 else 0
        if resp.data is not None:
            return len(str(resp.data))
        return 0

    @staticmethod
    def _record_access(handler, method: str, handler_name: str,
                       req: Request, resp: Response, shed: bool,
                       ddl, t0: float) -> None:
        """One sampled workload access record (observability/reqlog.py).
        Only runs when the recorder is enabled; everything costly
        (redaction, byte accounting) happens here, after the cheap
        gate.  Never raises into the serving path."""
        try:
            if resp.raw is not None:
                out = len(resp.raw)
            elif resp.file_path is not None:
                _off, length = resp.file_range or (0, -1)
                out = length if length >= 0 else \
                    os.path.getsize(resp.file_path)
            elif resp.data is not None:
                # cheap size estimate without re-serializing the body
                out = len(str(resp.data))
            else:
                out = 0
            try:
                bytes_in = int(handler.headers.get("Content-Length") or 0)
            except (TypeError, ValueError):
                bytes_in = 0
            peer = ""
            addr = getattr(handler, "client_address", None)
            if addr:
                peer = str(addr[0])
            path = _reqlog.redact_query(handler.path)
            dur_s = _time.perf_counter() - t0
            # the recorded budget is the caller's budget at INGRESS
            # (what a replay spec's deadline_s should reproduce), not
            # what was left after the handler ran
            _RECORDER.record(
                _reqlog.classify_route(method, req.path, handler_name,
                                       query=req.query),
                method, path, resp.status,
                bytes_in=bytes_in, bytes_out=out,
                duration_ms=dur_s * 1e3,
                deadline_s=(ddl.remaining() + dur_s
                            if ddl is not None else None),
                shed=shed, degraded=resp.status >= 500, peer=peer,
                handler=handler_name)
        except Exception:
            pass  # recording must never break the serving path

    @staticmethod
    def _drain_body(handler: BaseHTTPRequestHandler) -> None:
        try:
            te = (handler.headers.get("Transfer-Encoding") or "").lower()
            if "chunked" in te:
                # Request.body only understands Content-Length; a chunked body
                # can't be framed, so the connection must not be reused.
                handler.close_connection = True
                return
            left = int(handler.headers.get("Content-Length") or 0)
            while left > 0:
                n = len(handler.rfile.read(min(left, 1 << 16)) or b"")
                if n == 0:
                    break
                left -= n
        except Exception:
            try:
                handler.close_connection = True
            except Exception:
                pass

    @staticmethod
    def _send(handler: BaseHTTPRequestHandler, resp: Response) -> None:
        try:
            if resp.file_path is not None:
                import os as _os

                size = _os.path.getsize(resp.file_path)
                off, length = resp.file_range or (0, size)
                length = max(0, min(length, size - off))
                ctype = resp.headers.pop("Content-Type",
                                         "application/octet-stream")
                handler.send_response(resp.status)
                handler.send_header("Content-Type", ctype)
                handler.send_header("Content-Length", str(length))
                for k, v in resp.headers.items():
                    handler.send_header(k, v)
                handler.end_headers()
                if handler.command != "HEAD":
                    # reactor connections take the zero-copy road: the
                    # loop streams the region with os.sendfile and a
                    # slow client costs an outbox entry, not a thread
                    sendfile = getattr(handler, "sendfile", None)
                    if sendfile is not None and sendfile(
                            resp.file_path, off, length):
                        return
                    with open(resp.file_path, "rb") as f:
                        f.seek(off)
                        left = length
                        while left > 0:
                            piece = f.read(min(left, 1 << 20))
                            if not piece:
                                break
                            handler.wfile.write(piece)
                            left -= len(piece)
                return
            if resp.raw is not None:
                body = resp.raw
                ctype = resp.headers.pop("Content-Type", "application/octet-stream")
            else:
                body = json.dumps(resp.data if resp.data is not None else {}).encode()
                ctype = "application/json"
            handler.send_response(resp.status)
            handler.send_header("Content-Type", ctype)
            # HEAD responses may declare the real entity size explicitly
            explicit_len = resp.headers.pop("Content-Length", None)
            handler.send_header("Content-Length",
                                explicit_len if explicit_len is not None
                                else str(len(body)))
            for k, v in resp.headers.items():
                handler.send_header(k, v)
            handler.end_headers()
            if handler.command != "HEAD":
                handler.wfile.write(body)
        except (BrokenPipeError, ConnectionResetError):
            pass


# the extra verbs beyond the big five are the WebDAV set (RFC 4918) used by
# the webdav gateway; BaseHTTPRequestHandler dispatches by do_<METHOD> name
EXTRA_METHODS = ("OPTIONS", "PROPFIND", "PROPPATCH", "MKCOL", "MOVE", "COPY",
                 "LOCK", "UNLOCK")


# --- fast threaded HTTP/1.1 server -------------------------------------------
# Drop-in replacement for http.server: same Router/handler contract, but the
# request line and headers are parsed by hand instead of through
# email.parser (BaseHTTPRequestHandler's dominant per-request cost), and
# status+headers go out in ONE sendall.  On the 1-core bench this roughly
# doubles object-path req/s (ref: weed/server/volume_server_handlers_read.go
# serves the same hot path from net/http, which does the equivalent
# hand-rolled parsing in Go).  Set WEED_HTTPD=stdlib to fall back.


class CIHeaders:
    """Case-insensitive request-header view: .get/[]/in by any case,
    .items() preserves the wire case (SigV4 canonicalization lowercases
    for itself)."""

    __slots__ = ("_pairs", "_lower")

    def __init__(self, pairs: list):
        self._pairs = pairs
        self._lower = {}
        for k, v in pairs:
            lk = k.lower()
            # first value wins, matching email.Message.get
            if lk not in self._lower:
                self._lower[lk] = v

    def get(self, key: str, default=None):
        return self._lower.get(key.lower(), default)

    def __getitem__(self, key: str):
        return self._lower[key.lower()]

    def __contains__(self, key) -> bool:
        return key.lower() in self._lower

    def __iter__(self):
        return (k for k, _ in self._pairs)

    def items(self):
        return list(self._pairs)

    def keys(self):
        return [k for k, _ in self._pairs]

    def values(self):
        return [v for _, v in self._pairs]

    def __len__(self):
        return len(self._pairs)


_date_cache: tuple[int, str] = (0, "")


def _http_date() -> str:
    """RFC 7231 Date, cached per second (strftime per request is real
    cost at tens of thousands of req/s)."""
    global _date_cache
    now = int(_time.time())
    if _date_cache[0] != now:
        _date_cache = (now, _time.strftime(
            "%a, %d %b %Y %H:%M:%S GMT", _time.gmtime(now)))
    return _date_cache[1]


class _FastHandler:
    """Per-connection handler exposing exactly the BaseHTTPRequestHandler
    surface Router uses: command/path/headers/rfile/wfile/client_address/
    close_connection/server + send_response/send_header/end_headers."""

    __slots__ = ("server", "rfile", "wfile", "client_address", "command",
                 "path", "headers", "close_connection", "_out")

    def __init__(self, server, rfile, wfile, client_address):
        self.server = server
        self.rfile = rfile
        self.wfile = wfile
        self.client_address = client_address
        self.command = ""
        self.path = ""
        self.headers: Optional[CIHeaders] = None
        self.close_connection = True
        self._out: list = []

    def send_response(self, status: int, message: str = "") -> None:
        self._out = [b"HTTP/1.1 %d %s\r\nDate: %s\r\n"
                     % (status, (message or _REASONS.get(status, "OK")).encode(),
                        _http_date().encode())]

    def send_header(self, key: str, value) -> None:
        self._out.append(f"{key}: {value}\r\n".encode())
        if key.lower() == "connection" and str(value).lower() == "close":
            self.close_connection = True

    def end_headers(self) -> None:
        self._out.append(b"\r\n")
        self.wfile.write(b"".join(self._out))
        self._out = []


_REASONS = {200: "OK", 201: "Created", 204: "No Content",
            206: "Partial Content", 301: "Moved Permanently", 302: "Found",
            303: "See Other", 304: "Not Modified", 307: "Temporary Redirect",
            400: "Bad Request", 401: "Unauthorized", 403: "Forbidden",
            404: "Not Found", 405: "Method Not Allowed", 409: "Conflict",
            412: "Precondition Failed", 416: "Range Not Satisfiable",
            500: "Internal Server Error", 503: "Service Unavailable"}


class _SockWriter:
    """Unbuffered writer over a socket: each .write is one sendall (the
    Router batches status+headers itself; bodies are already chunked)."""

    __slots__ = ("_sock",)

    def __init__(self, sock):
        self._sock = sock

    def write(self, data) -> int:
        self._sock.sendall(data)
        return len(data)

    def flush(self) -> None:
        pass


class FastHTTPServer:
    """Threaded accept loop + thread-per-connection keep-alive handling.
    Exposes the ThreadingHTTPServer surface the rest of the codebase
    touches: server_address, _stopping, shutdown(), server_close()."""

    daemon_threads = True

    def __init__(self, addr, router: Router, tls_context=None):
        import socket

        self.router = router
        self._tls = tls_context
        self._stopping = False
        self._done = threading.Event()
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind(addr)
        self._sock.listen(256)
        self.server_address = self._sock.getsockname()
        self.socket = self._sock

    def serve_forever(self) -> None:
        import selectors

        # poll + flag instead of a bare blocking accept: close()ing a
        # socket does NOT wake a thread blocked in accept(), and the
        # kernel keeps the LISTEN alive while that thread holds it — the
        # old port would stay bound and a same-port restart would fail
        sel = selectors.DefaultSelector()
        sel.register(self._sock, selectors.EVENT_READ)
        try:
            while not self._stopping:
                if not sel.select(timeout=0.25):
                    continue
                try:
                    conn, peer = self._sock.accept()
                except OSError:
                    break  # listener closed
                t = threading.Thread(target=self._handle, args=(conn, peer),
                                     daemon=True)
                t.start()
        finally:
            sel.close()
            try:
                self._sock.close()
            except OSError:
                pass
            self._done.set()

    def _handle(self, conn, peer) -> None:
        import socket

        try:
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            if self._tls is not None:
                conn = self._tls.wrap_socket(conn, server_side=True)
            rfile = conn.makefile("rb", buffering=1 << 16)
            wfile = _SockWriter(conn)
            h = _FastHandler(self, rfile, wfile, peer)
            while not self._stopping:
                line = rfile.readline(1 << 16)
                if not line or line in (b"\r\n", b"\n"):
                    break
                if not line.endswith(b"\n"):
                    # a 64KB+ request line would otherwise be split and
                    # parsed as two garbage requests
                    conn.sendall(b"HTTP/1.1 414 URI Too Long\r\n"
                                 b"Content-Length: 0\r\n"
                                 b"Connection: close\r\n\r\n")
                    break
                try:
                    method, _, rest = line.rstrip(b"\r\n").partition(b" ")
                    target, _, version = rest.rpartition(b" ")
                    h.command = method.decode("ascii")
                    h.path = target.decode("iso-8859-1")
                except (UnicodeDecodeError, ValueError):
                    break
                pairs = []
                overflow = False
                while True:
                    hl = rfile.readline(1 << 16)
                    if hl in (b"\r\n", b"\n", b""):
                        break
                    if len(pairs) >= 100 or not hl.endswith(b"\n"):
                        # stdlib's email.parser enforced ~100 headers;
                        # unbounded headers (or an unterminated 64KB+
                        # line) is a memory-exhaustion vector
                        overflow = True
                        break
                    k, _, v = hl.partition(b":")
                    pairs.append((k.decode("iso-8859-1"),
                                  v.strip().decode("iso-8859-1")))
                if overflow:
                    conn.sendall(b"HTTP/1.1 431 Request Header Fields Too "
                                 b"Large\r\nContent-Length: 0\r\n"
                                 b"Connection: close\r\n\r\n")
                    break
                h.headers = CIHeaders(pairs)
                if "chunked" in (h.headers.get("Transfer-Encoding") or "").lower():
                    # Request.body only frames Content-Length bodies; a
                    # chunked body can't be skipped safely, so refuse and
                    # close rather than desync the keep-alive stream
                    conn.sendall(b"HTTP/1.1 501 Not Implemented\r\n"
                                 b"Content-Length: 0\r\n"
                                 b"Connection: close\r\n\r\n")
                    break
                # HTTP/1.1 defaults to keep-alive; 1.0 to close
                conn_hdr = (h.headers.get("Connection") or "").lower()
                h.close_connection = (
                    conn_hdr == "close"
                    or (version == b"HTTP/1.0" and conn_hdr != "keep-alive"))
                if (h.headers.get("Expect") or "").lower() == "100-continue":
                    # curl sends this for big uploads and stalls ~1s
                    # waiting for the interim response
                    conn.sendall(b"HTTP/1.1 100 Continue\r\n\r\n")
                self.router.dispatch(h, h.command)
                if h.close_connection:
                    break
        except (OSError, ValueError):
            pass
        finally:
            # half-close + brief drain before close: closing with unread
            # bytes in the receive queue sends RST, which can destroy an
            # already-sent error response (414/431/501 paths reject
            # requests whose remainder is still in flight)
            try:
                conn.shutdown(socket.SHUT_WR)
                conn.settimeout(0.5)
                deadline = _time.monotonic() + 2.0
                drained = 0
                while _time.monotonic() < deadline and drained < (1 << 22):
                    piece = conn.recv(1 << 16)
                    if not piece:
                        break
                    drained += len(piece)
            except OSError:
                pass
            try:
                conn.close()
            except OSError:
                pass

    def shutdown(self) -> None:
        """Stop accepting and RELEASE the port before returning (callers
        immediately rebind on master restart)."""
        self._stopping = True  # weedlint: disable=W502 monotonic shutdown latch: single atomic bool store, the accept loop reads it once per iteration and either value is safe
        self._done.wait(timeout=5.0)

    def server_close(self) -> None:
        try:
            self._sock.close()
        except OSError:
            pass


def _serve_stdlib(router: Router, host: str, port: int,
                  tls_context=None) -> ThreadingHTTPServer:
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"
        # headers and body flush as separate segments; with Nagle on, the
        # client's delayed ACK stalls every keep-alive exchange ~40ms —
        # the difference between ~20 and ~1000 req/s per connection
        disable_nagle_algorithm = True

        def log_message(self, fmt, *args):  # quiet by default
            pass

        def do_GET(self):
            router.dispatch(self, "GET")

        def do_HEAD(self):
            router.dispatch(self, "HEAD")

        def do_POST(self):
            router.dispatch(self, "POST")

        def do_PUT(self):
            router.dispatch(self, "PUT")

        def do_DELETE(self):
            router.dispatch(self, "DELETE")

    for _m in EXTRA_METHODS:
        setattr(Handler, f"do_{_m}",
                (lambda m: lambda self: router.dispatch(self, m))(_m))

    server = ThreadingHTTPServer((host, port), Handler)
    if tls_context is not None:
        server.socket = tls_context.wrap_socket(server.socket,
                                                server_side=True)
    server.daemon_threads = True
    thread = threading.Thread(target=server.serve_forever, daemon=True,
                              name=f"{router.name}:{port}")
    thread.start()
    return server


def serve(router: Router, host: str, port: int, tls_context=None):
    """Start the HTTP front; with tls_context (an ssl.SSLContext from
    security.tls.server_context) the listening socket speaks HTTPS and —
    when the context demands client certs — enforces mTLS.

    Default: register the listener on the shared event-loop dataplane
    (utils/eventloop.py) — keep-alive/pipelined parsing on the reactor,
    dispatch on its bounded worker pool, zero-copy writeback.  TLS
    sockets stay on the threaded server (the reactor's non-blocking
    parse has no handshake state machine).  WEED_DATAPLANE=threaded or
    WEED_HTTPD=threaded force the thread-per-connection FastHTTPServer;
    WEED_HTTPD=stdlib falls all the way back to http.server."""
    if os.environ.get("WEED_HTTPD") == "stdlib":
        return _serve_stdlib(router, host, port, tls_context)
    from . import eventloop

    if tls_context is None and eventloop.reactor_enabled() \
            and os.environ.get("WEED_HTTPD") != "threaded":
        return eventloop.ReactorHTTPServer((host, port), router)
    server = FastHTTPServer((host, port), router, tls_context)
    thread = threading.Thread(target=server.serve_forever, daemon=True,
                              name=f"{router.name}:{server.server_address[1]}")
    thread.start()
    return server


# --- cluster TLS ------------------------------------------------------------
# One switch for the whole process (security.toml [tls] analog): when a
# client SSL context is installed, every inter-server URL is upgraded from
# http:// to https:// and verified (optionally with a client cert = mTLS).
_client_tls = None


def set_client_tls(context) -> None:
    """Install (or clear, with None) the process-wide client SSL context."""
    global _client_tls
    _client_tls = context


def _prep_url(url: str):
    """Returns (url, ssl_context) with the scheme upgraded when TLS is on."""
    if _client_tls is not None and url.startswith("http://"):
        return "https://" + url[len("http://"):], _client_tls
    return url, (_client_tls if url.startswith("https://") else None)


# --- pooled keep-alive client ------------------------------------------------
# One persistent TCP_NODELAY connection per (thread, scheme, netloc).  A
# fresh TCP connection per request costs handshake + slow-start and (with
# the tiny request/response segments the control plane sends) falls into
# Nagle/delayed-ACK stalls; pooling is the difference between ~400 and
# many thousands of cluster req/s.  The connection itself is raw-socket
# HTTP/1.1 rather than http.client: the stdlib client re-parses every
# response through email.parser, which measured ~4x slower than this
# hand-rolled exchange on the cluster hot path.


class _RawConn:
    """Minimal keep-alive HTTP/1.1 exchange over one socket: hand-built
    request bytes out, hand-parsed status/headers/body in.  Supports
    Content-Length and chunked bodies, and read-to-close for legacy
    peers."""

    __slots__ = ("sock", "rfile", "host")

    def __init__(self, scheme: str, netloc: str, timeout: float, ssl_ctx):
        import socket as _socket

        host, _, port_s = netloc.partition(":")
        port = int(port_s) if port_s else (443 if scheme == "https" else 80)
        self.host = netloc
        sock = _socket.create_connection((host, port), timeout=timeout)
        sock.setsockopt(_socket.IPPROTO_TCP, _socket.TCP_NODELAY, 1)
        if scheme == "https":
            import ssl as _ssl

            ctx = ssl_ctx or _ssl.create_default_context()
            sock = ctx.wrap_socket(sock, server_hostname=host)
        self.sock = sock
        self.rfile = sock.makefile("rb", buffering=1 << 16)

    def request(self, method: str, target: str, body: Optional[bytes],
                headers: dict) -> tuple[int, bytes, dict, bool]:
        """-> (status, body, headers, will_close)"""
        out = [f"{method} {target} HTTP/1.1\r\nHost: {self.host}\r\n"
               .encode("latin-1")]
        has_len = False
        for k, v in headers.items():
            lk = k.lower()
            if lk == "host":
                continue  # already sent
            if lk == "content-length":
                has_len = True
            out.append(f"{k}: {v}\r\n".encode("latin-1"))
        if body is not None and not has_len:
            out.append(b"Content-Length: %d\r\n" % len(body))
        elif body is None and method in ("POST", "PUT"):
            out.append(b"Content-Length: 0\r\n")
        out.append(b"\r\n")
        if body:
            out.append(body)
        self.sock.sendall(b"".join(out))
        while True:  # interim 1xx responses are swallowed
            line = self.rfile.readline(1 << 16)
            if not line:
                raise ConnectionError("connection closed by peer")
            parts = line.split(None, 2)
            if len(parts) < 2 or not parts[0].startswith(b"HTTP/"):
                raise ConnectionError(f"bad status line {line!r}")
            status = int(parts[1])
            version = parts[0]
            hdrs: dict = {}
            while True:
                hl = self.rfile.readline(1 << 16)
                if hl in (b"\r\n", b"\n", b""):
                    break
                k, _, v = hl.partition(b":")
                hdrs[k.decode("latin-1")] = v.strip().decode("latin-1")
            if status >= 200:
                break
        lower = {k.lower(): v for k, v in hdrs.items()}
        conn_hdr = lower.get("connection", "").lower()
        will_close = (conn_hdr == "close"
                      or (version == b"HTTP/1.0"
                          and conn_hdr != "keep-alive"))
        # body framing
        if method == "HEAD" or status in (204, 304):
            return status, b"", hdrs, will_close
        if lower.get("transfer-encoding", "").lower() == "chunked":
            pieces = []
            while True:
                szline = self.rfile.readline(1 << 16)
                try:
                    n = int(szline.split(b";")[0].strip() or b"0", 16)
                except ValueError:
                    raise ConnectionError(f"bad chunk size {szline!r}")
                if n == 0:
                    # trailers until blank line
                    while self.rfile.readline(1 << 16) not in (b"\r\n", b"\n",
                                                               b""):
                        pass
                    break
                pieces.append(self._read_exact(n))
                self.rfile.read(2)  # CRLF
            return status, b"".join(pieces), hdrs, will_close
        if "content-length" in lower:
            n = int(lower["content-length"])
            return status, self._read_exact(n), hdrs, will_close
        # no framing: body runs to connection close
        data = self.rfile.read()
        return status, data or b"", hdrs, True

    def _read_exact(self, n: int) -> bytes:
        data = self.rfile.read(n)
        if data is None or len(data) != n:
            raise ConnectionError("short body read")
        return data

    def settimeout(self, t: float) -> None:
        self.sock.settimeout(t)

    def close(self) -> None:
        try:
            self.rfile.close()
        except OSError:
            pass
        try:
            self.sock.close()
        except OSError:
            pass


class _ConnPool(threading.local):
    def __init__(self):
        self.conns: dict = {}


_pool = _ConnPool()


def _egress_span(method: str, parsed, **attrs):
    """Distributed-trace egress gate, shared by _pooled_request and
    http_download so EVERY outbound hop in the codebase (client SDK,
    replication, gateways, EC copies/remote shard reads, master scrapes)
    rides ONE copy of the sampling logic: open an rpc.client span iff
    this thread holds a trace context AND its head decision sampled the
    request — the open span's id becomes the downstream parent (the
    stitching edge), so callers must inject the Traceparent INSIDE the
    returned span.  An unsampled (or undecided) thread pays one
    thread-local read.  Returns (span_cm, ctx); ctx None means "no
    trace context at all" — skip injection entirely."""
    ctx = _trace_context.current()
    if ctx is None:
        return _NOOP_SPAN, None
    tracer = _get_tracer()
    if tracer.enabled and _trace_context.current_sampled() is not None:
        return tracer.span("rpc.client", method=method,
                           peer=parsed.netloc, path=parsed.path,
                           **attrs), ctx
    return _NOOP_SPAN, ctx


def _pooled_request(method: str, url: str, body: Optional[bytes],
                    headers: Optional[dict], timeout: float
                    ) -> tuple[int, bytes, dict]:
    """One request over the pool; raises OSError family on failure.
    A request that fails with a CONNECTION-staleness error on a REUSED
    connection retries once on a fresh one (the server closed the idle
    keep-alive — it never saw the request).  Timeouts NEVER retry: the
    server may be mid-mutation, and transparently resending a POST
    would execute it twice.  Failures on a brand-new connection
    propagate."""
    import socket as _socket

    url, ssl_ctx = _prep_url(url)
    parsed = urllib.parse.urlsplit(url)
    key = (parsed.scheme, parsed.netloc)
    target = (parsed.path or "/") + (f"?{parsed.query}" if parsed.query
                                     else "")
    from . import faultinject as fi

    if fi._points:
        fi.hit("net.request")
        # peer-scoped network faults (the scenario engine's wire): a
        # partition/drop fails the send instantly; a delay is applied
        # deadline-aware, so a slow wire stalls the request but never
        # the caller past its budget — like a real socket timeout
        fi.hit_peer("net.partition", parsed.netloc)
        fi.hit_peer("net.drop", parsed.netloc)
        _net_delay = fi.peer_delay("net.delay", parsed.netloc)
        if _net_delay:
            _deadline.sleep_within(_net_delay)
    # deadline clamp: the per-call timeout never exceeds the remaining
    # propagated budget (a 2s client deadline must not become 30s of
    # downstream waiting); a spent budget raises before sending
    timeout = _deadline.clamp(timeout)
    span_cm, ctx = _egress_span(method, parsed)
    if ctx is not None or _deadline.current() is not None:
        headers = dict(headers or {})
    with span_cm:
        if ctx is not None:
            _trace_context.inject_trace_headers(headers)
        if _deadline.current() is not None:
            _deadline.inject_deadline_headers(headers)
        for _ in range(2):
            conn = _pool.conns.get(key)
            reused = conn is not None
            if conn is None:
                conn = _RawConn(parsed.scheme, parsed.netloc, timeout,
                                ssl_ctx)
                _pool.conns[key] = conn
            try:
                conn.settimeout(timeout)
                status, data, hdrs, will_close = conn.request(
                    method, target, body, headers or {})
                if will_close:
                    conn.close()
                    _pool.conns.pop(key, None)
                return status, data, hdrs
            except (TimeoutError, _socket.timeout):
                conn.close()
                _pool.conns.pop(key, None)
                ddl = _deadline.current()
                if ddl is not None and ddl.expired():
                    # the deadline was the binding constraint: surface
                    # it as a budget exhaustion (servers answer 504),
                    # not a generic transport timeout
                    raise DeadlineExceeded(
                        f"deadline exceeded awaiting "
                        f"{parsed.netloc}") from None
                raise
            except Exception:
                conn.close()
                _pool.conns.pop(key, None)
                if not reused:
                    raise
    raise OSError("unreachable")  # pragma: no cover


def _pooled_with_redirects(method: str, url: str, body: Optional[bytes],
                           headers: Optional[dict], timeout: float,
                           follow_redirects: bool
                           ) -> tuple[int, bytes, dict]:
    for _ in range(5):
        status, data, hdrs = _pooled_request(method, url, body, headers,
                                             timeout)
        if follow_redirects and status in (301, 302, 303, 307, 308) \
                and hdrs.get("Location"):
            url = urllib.parse.urljoin(url, hdrs["Location"])
            if status == 303:
                method, body = "GET", None
            continue
        return status, data, hdrs
    return status, data, hdrs


# --- client helpers ---------------------------------------------------------

def stop_server(server) -> None:
    """Shut down a serve() result: stop the loop AND close the listening
    socket — otherwise clients queue in the accept backlog and hang
    instead of failing over.  Surviving keep-alive handler threads see
    _stopping and answer 503 + Connection: close, so pooled clients
    migrate to whoever owns the port next."""
    server._stopping = True
    server.shutdown()
    server.server_close()


def http_json(method: str, url: str, payload: Optional[dict] = None,
              timeout: float = 30.0) -> dict:
    data = json.dumps(payload).encode() if payload is not None else None
    headers = {"Content-Type": "application/json"} if data is not None else {}
    try:
        status, body, _ = _pooled_with_redirects(method, url, data, headers,
                                                 timeout, True)
    except (ConnectionError, TimeoutError, OSError) as e:
        raise HttpError(503, f"{url} unreachable: {e}") from None
    if status >= 400:
        try:
            err = json.loads(body).get("error", body.decode(errors="replace"))
        except Exception:
            err = body.decode(errors="replace")
        raise HttpError(status, err) from None
    return json.loads(body) if body else {}


def http_json_retry(method: str, url: str, payload: Optional[dict] = None,
                    timeout: float = 30.0, attempts: int = 3,
                    budget_kind: str = "http") -> dict:
    """http_json with bounded transient-failure retries that draw from
    the per-destination retry budget (utils/backoff.py): each RETRY
    (never the first attempt) takes a token for the peer; a drained
    bucket degrades the call to what it already did and journals
    `retry_budget_exhausted` — retries must not multiply load onto a
    peer that is already down.  Only unreachable/503 answers retry
    (anything else is a real server answer); only idempotent methods
    may retry (a timed-out POST may have executed — resending would
    run it twice).  Retries never extend past an active deadline:
    http_json's egress clamp raises DeadlineExceeded the moment the
    budget is spent."""
    from .backoff import jittered_backoff, retry_allowed

    dest = urllib.parse.urlsplit(url).netloc
    retriable = method.upper() in ("GET", "HEAD")
    last: Optional[HttpError] = None
    for i in range(max(1, int(attempts))):
        if i:
            if not retriable or not retry_allowed(dest, budget_kind):
                break
            _deadline.sleep_within(jittered_backoff(0.05, 1.0, i - 1))
        try:
            return http_json(method, url, payload, timeout=timeout)
        except HttpError as e:
            last = e
            if e.status != 503:
                raise
    raise last  # type: ignore[misc]


UNSATISFIABLE_RANGE = (-1, 0)


def parse_range(range_header: str, file_size: int) -> Optional[tuple[int, int]]:
    """Parse an RFC 7233 single range against file_size -> (offset, size);
    None for absent/invalid headers (serve the whole body), or
    UNSATISFIABLE_RANGE when the range starts past EOF (serve 416).
    Handles bytes=N-, bytes=N-M, bytes=-N."""
    if not range_header.startswith("bytes="):
        return None
    lo, dash, hi = range_header[6:].partition("-")
    if not dash:
        return None
    try:
        if lo == "":  # suffix range: last N bytes
            n = int(hi)
            if n == 0 or file_size == 0:
                # RFC 7233: a zero-length suffix (or any suffix of an empty
                # file) has no satisfiable byte range
                return UNSATISFIABLE_RANGE
            offset = max(0, file_size - n)
            return offset, file_size - offset
        offset = int(lo)
        if offset >= file_size:
            return UNSATISFIABLE_RANGE
        if hi == "":
            return offset, file_size - offset
        end = min(int(hi), file_size - 1)
        return offset, end - offset + 1
    except ValueError:
        return None


def http_bytes(method: str, url: str, payload: Optional[bytes] = None,
               headers: Optional[dict] = None, timeout: float = 60.0,
               follow_redirects: bool = True) -> tuple[int, bytes, dict]:
    try:
        return _pooled_with_redirects(method, url, payload, headers,
                                      timeout, follow_redirects)
    except (ConnectionError, TimeoutError, OSError) as e:
        # dead/unreachable server: synthetic status 0 so callers fail over
        return 0, str(e).encode(), {}


def http_download(method: str, url: str, dest_path: str,
                  timeout: float = 3600.0,
                  piece_bytes: int = 1 << 20) -> int:
    """Stream a (possibly huge) response body straight to dest_path in
    bounded pieces — the client half of Response(file_path=...) streaming.
    Writes to dest_path.part and renames on success so a dropped transfer
    never leaves a torn file under the final name.  Returns the HTTP
    status (0 = unreachable)."""
    url, ssl_ctx = _prep_url(url)
    req = urllib.request.Request(url, method=method)
    parsed = urllib.parse.urlsplit(url)
    from . import faultinject as fi

    if fi._points:
        # same peer-scoped network faults as _pooled_request: bulk
        # transfers ride the same simulated wire
        fi.hit_peer("net.partition", parsed.netloc)
        fi.hit_peer("net.drop", parsed.netloc)
        _net_delay = fi.peer_delay("net.delay", parsed.netloc)
        if _net_delay:
            _deadline.sleep_within(_net_delay)
    # deadline clamp + header: a budgeted caller's bulk fetch inherits
    # the remaining budget, never the 1h default
    timeout = _deadline.clamp(timeout)
    # same trace egress as _pooled_request: bulk transfers (volume copy,
    # EC shard copy) appear on the stitched trace as rpc.client hops and
    # carry the caller's Traceparent downstream
    span_cm, ctx = _egress_span(method, parsed, download=True)
    tmp = dest_path + ".part"
    with span_cm:
        if ctx is not None:
            for k, v in _trace_context.inject_trace_headers({}).items():
                req.add_header(k, v)
        for k, v in _deadline.inject_deadline_headers({}).items():
            req.add_header(k, v)
        return _http_download_body(req, timeout, ssl_ctx, tmp,
                                   dest_path, piece_bytes)


def _http_download_body(req, timeout, ssl_ctx, tmp: str, dest_path: str,
                        piece_bytes: int) -> int:
    try:
        with urllib.request.urlopen(req, timeout=timeout,
                                    context=ssl_ctx) as r:
            with open(tmp, "wb") as f:
                while True:
                    piece = r.read(piece_bytes)
                    if not piece:
                        break
                    f.write(piece)
            os.replace(tmp, dest_path)
            return r.status
    except urllib.error.HTTPError as e:
        return e.code
    except (urllib.error.URLError, ConnectionError, TimeoutError, OSError):
        return 0
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass
