"""Observability shell commands: trace analysis + cluster telemetry.

    trace.analyze -server host:port       # analyze a live server's ring
    trace.analyze -file trace.json        # analyze a saved trace offline
    cluster.health                        # per-volume-server health rollup
    cluster.raft                          # per-master raft quorum view

trace.analyze turns a span ring into the attribution report
(observability/analysis.py): stage occupancy, the critical-path stage,
gap classification, and the clean-vs-degraded verdict — the answer the
next perf PR needs, without eyeballing raw span dumps.  -file accepts
either a Tracer.to_dict() document or the Chrome trace JSON written by
Tracer.to_chrome() / GET /debug/traces.
"""

from __future__ import annotations

import json
import time

from ..utils.httpd import http_bytes, http_json
from .commands import CommandEnv, command


@command("trace.analyze")
def cmd_trace_analyze(env: CommandEnv, flags: dict) -> str:
    """trace.analyze [-server host:port] [-file trace.json] [-json]
    # critical-path attribution report for a server's span ring (or a
    # saved trace file); -json emits the raw report document"""
    from ..observability.analysis import analyze, render_report

    path = flags.get("file") or ""
    server = flags.get("server") or ""
    if path:
        with open(path) as f:
            doc = json.load(f)
        report = analyze(doc)
    elif server:
        status, body, _ = http_bytes(
            "GET", f"http://{server}/debug/traces/analyze", timeout=60.0)
        if status != 200:
            raise RuntimeError(
                f"{server}/debug/traces/analyze: status {status}: "
                f"{body[:200].decode(errors='replace')}")
        report = json.loads(body)
    else:
        raise ValueError(
            "trace.analyze needs -server host:port or -file trace.json")
    if flags.get("json") == "true":
        return json.dumps(report, indent=2)
    return render_report(report).rstrip("\n")


@command("trace.fetch")
def cmd_trace_fetch(env: CommandEnv, flags: dict) -> str:
    """trace.fetch <trace_id> | -trace <trace_id> [-json]
    [-chrome [-out file.json]] | -list
    # fetch one stitched cluster trace from the master's collector and
    # render the cross-server analysis (per-hop occupancy, network-vs-
    # server split, bounding hop, degraded verdict); -chrome saves the
    # Chrome trace-event view instead; -list shows recent trace ids.
    # A bare `trace.fetch` defaults to the PREVIOUS command's trace id
    # (env.prev_trace_id — the repl prints it after each command), so
    # the "what did that command do across the cluster?" follow-up
    # needs no copy-paste."""
    if flags.get("list") == "true" or flags.get("") == "list":
        doc = env.master_get("/cluster/traces")
        lines = []
        for t in doc.get("traces", []):
            lines.append(f"{t['trace_id']}  root={t.get('root')} "
                         f"spans={t['span_count']} wall={t['wall_s']}s "
                         f"servers={','.join(t['servers'])}")
        return "\n".join(lines) or "no traces collected"
    trace_id = (flags.get("trace") or flags.get("")
                or getattr(env, "prev_trace_id", "") or "")
    if not trace_id:
        raise ValueError("trace.fetch needs a trace id — run a command "
                         "first, pass one, or -list recent ones")
    if flags.get("chrome") == "true":
        doc = env.master_get(f"/cluster/traces/{trace_id}?format=chrome")
        out = flags.get("out") or f"trace_{trace_id[:8]}.json"
        with open(out, "w") as f:
            json.dump(doc, f)
        return f"wrote {out} ({len(doc.get('traceEvents', []))} events)"
    doc = env.master_get(f"/cluster/traces/{trace_id}")
    if flags.get("json") == "true":
        return json.dumps(doc, indent=2)
    from ..observability.analysis import render_cluster_report

    return render_cluster_report(doc["analysis"]).rstrip("\n")


def _raft_views(env: CommandEnv) -> dict[str, dict]:
    """/cluster/status from every reachable master: the configured
    candidates (env.master_url may be a comma-separated HA list) plus
    every peer any of them names.  Unreachable masters stay in the map
    with an `error` key — a failover drill wants to SEE the dead one."""
    queue = [u for u in env.master_url.split(",") if u]
    views: dict[str, dict] = {}
    while queue:
        url = queue.pop(0)
        if url in views:
            continue
        try:
            views[url] = http_json(
                "GET", f"http://{url}/cluster/status", timeout=5.0)
        except Exception as e:  # dead master: report, keep walking
            views[url] = {"error": str(e) or e.__class__.__name__}
            continue
        for p in views[url].get("Peers", []):
            if p not in views and p not in queue:
                queue.append(p)
    return views


def _quorum_line(views: dict[str, dict]) -> str:
    """`masters: 3 (leader host:port, term 7)` — the one-line quorum
    summary cluster.health and cluster.raft share."""
    up = {u: v for u, v in views.items() if "error" not in v}
    leaders = sorted({v.get("Leader") or "" for v in up.values()} - {""})
    term = max((int(v.get("Term") or 0) for v in up.values()), default=0)
    leader = leaders[0] if len(leaders) == 1 else \
        (f"DISPUTED {'/'.join(leaders)}" if leaders else "none")
    line = f"masters: {len(views)} (leader {leader}, term {term})"
    down = sorted(u for u, v in views.items() if "error" in v)
    if down:
        line += f"  down: {', '.join(down)}"
    return line


@command("cluster.raft")
def cmd_cluster_raft(env: CommandEnv, flags: dict) -> str:
    """cluster.raft [-json]  # per-master raft view: role, term, known
    leader, commit/applied indexes, log span, snapshot transfers —
    the quorum's replication progress at a glance"""
    views = _raft_views(env)
    if flags.get("json") == "true":
        return json.dumps({"masters": views}, indent=2)
    lines = [_quorum_line(views)]
    for url, v in sorted(views.items()):
        if "error" in v:
            lines.append(f"  {url}: unreachable ({v['error']})")
            continue
        lines.append(
            f"  {url}: {v.get('Role', '?')} term={v.get('Term', 0)} "
            f"commit={v.get('CommitIndex', 0)} "
            f"applied={v.get('LastApplied', 0)} "
            f"log[{v.get('LogFirstIndex', 1)}..{v.get('LastIndex', 0)}] "
            f"snap@{v.get('SnapshotIndex', 0)} "
            f"installed={v.get('SnapshotsInstalled', 0)} "
            f"sent={v.get('SnapshotsSent', 0)}")
    return "\n".join(lines)


@command("cluster.health")
def cmd_cluster_health(env: CommandEnv, flags: dict) -> str:
    """cluster.health [-json]  # master's per-volume-server telemetry
    rollup: reachability/staleness + pipeline health counters"""
    doc = env.master_get("/cluster/health")
    if flags.get("json") == "true":
        return json.dumps(doc, indent=2)
    lines = [f"peers: {doc['peer_count']}  "
             f"degraded: {doc['degraded']}  "
             f"stale: {', '.join(doc['stale_peers']) or 'none'}"]
    # one-line master-quorum rollup (best-effort: a single-master
    # deployment has no peers and still renders `masters: 1`)
    try:
        lines.append(_quorum_line(_raft_views(env)))
    except Exception:
        pass
    # one-line alerting rollup (best-effort: an old master without the
    # engine must not break the health view)
    try:
        al = env.master_get("/cluster/alerts")
        firing = [a["name"] for a in al.get("alerts", [])
                  if a["state"] == "firing"]
        lines.append(f"alerts: {al.get('firing', 0)} firing"
                     + (f" ({', '.join(firing)})" if firing else ""))
    except Exception:
        pass
    # one-line capacity hint when a probe result is parked on the
    # master (weed shell capacity.probe / the bench capacity section);
    # best-effort — 404 just means nobody probed yet
    try:
        cap = env.master_get("/cluster/capacity")
        slo = cap.get("slo") or {}
        parts = [f"{route}~{res['capacity_rps']:g}rps"
                 for route, res in sorted((cap.get("routes") or {}).items())
                 if isinstance(res, dict) and res.get("capacity_rps")]
        if parts:
            age = int(time.time() - float(cap.get("posted_at")
                                          or cap.get("probed_at") or 0))
            lines.append(
                f"capacity: {' '.join(parts)} "
                f"(SLO p99<{slo.get('max_p99_ms', '?')}ms, "
                f"probed {age}s ago)")
    except Exception:
        pass
    # one-line resource-ledger hint (best-effort): worst loop-lag p99
    # across the peers plus the route currently carrying the most CPU
    # — `cluster.top` is the drill-down
    try:
        led = env.master_get("/cluster/ledger?top=1")
        worst = max((s.get("loop_lag_p99_ms", 0.0)
                     for s in led.get("servers") or []), default=0.0)
        stalls = sum(s.get("stalls", 0)
                     for s in led.get("servers") or [])
        routes = led.get("routes") or []
        if routes:
            r = routes[0]
            lines.append(
                f"ledger: loop_lag_p99={worst:g}ms stalls={stalls} "
                f"top_route={r['route']} "
                f"({r.get('cpu_share', 0.0):.0%} cpu, "
                f"{r.get('req_rate', 0.0):g} req/s) — cluster.top")
    except Exception:
        pass
    t = doc["totals"]
    lines.append(f"totals: worker_restarts={t['worker_restarts']} "
                 f"engine_fallbacks={t['engine_fallbacks']} "
                 f"degraded_binds={t['degraded_binds']} "
                 f"scrub_unrepairable={t.get('scrub_unrepairable', 0)}")
    for url, p in sorted(doc["peers"].items()):
        ph = p["pipeline_health"]
        state = "up" if p["up"] else f"DOWN ({p.get('error', '')})"
        if p["stale"]:
            state += " STALE"
        line = (f"  {url}: {state} age={p.get('age_s')}s "
                f"restarts={ph['worker_restarts']} "
                f"fallbacks={ph['engine_fallbacks']} "
                f"degraded_binds={ph['degraded_binds']}")
        scrub = p.get("scrub")
        if scrub:
            verdicts = ",".join(f"{k}={v}" for k, v
                                in sorted(scrub["verdicts"].items())) \
                or "none"
            line += (f" scrub[running={scrub['running']} "
                     f"passes={scrub['passes']} {verdicts}]")
        lines.append(line)
    return "\n".join(lines)
