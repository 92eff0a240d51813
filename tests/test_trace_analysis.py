"""Critical-path analyzer acceptance: the drain-wait stall explains itself.

observability/analysis.py is the layer the next perf PR consumes — these
tests pin the contract end to end:

  - a synthetic CPU-only pipeline run with an injected slow drain
    (ec.drain fault delay) is attributed to the `drain` stage with >=80%
    of the wall, by name;
  - a forced per-dispatch CPU fallback reports degraded=true;
  - the gap analysis of the fetch track names the producer's stages;
  - offline analysis (Tracer.to_dict() round-trip and the Chrome
    trace JSON from Tracer.to_chrome()) produces the same report as
    the live ring;
  - the report is served on GET /debug/traces/analyze and through the
    `weed shell` trace.analyze command.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from seaweedfs_tpu.ec.streaming import StreamingEncoder
from seaweedfs_tpu.observability import (Tracer, analyze,
                                         attribution_summary,
                                         disable_tracing, enable_tracing,
                                         render_report)
from seaweedfs_tpu.utils import faultinject as fi

K, R = 10, 4
LARGE, SMALL = 100 << 20, 1 << 20


def _make_volume(tmp_path, size_mb: int) -> str:
    dat = str(tmp_path / "v.dat")
    rng = np.random.default_rng(0xA11)
    with open(dat, "wb") as f:
        f.write(rng.integers(0, 256, size_mb << 20,
                             dtype=np.uint8).tobytes())
    return dat


@pytest.fixture(scope="module", autouse=True)
def _compiled(tmp_path_factory):
    """Compile the kernel for both dispatch shapes (mapped rows, slot)
    before any traced run, so no run's wall carries the compile."""
    _staged_encode(tmp_path_factory.mktemp("warm"), Tracer(enabled=False))


def _staged_encode(tmp_path, tracer, size_mb=12, **kw) -> StreamingEncoder:
    """The staged plane: the device engine on one (CPU) device."""
    dat = _make_volume(tmp_path, size_mb)
    enc = StreamingEncoder(K, R, engine="device", devices="1",
                           dispatch_mb=1, tracer=tracer, **kw)
    enc.encode_file(dat, str(tmp_path / "v"),
                    large_block_size=LARGE, small_block_size=SMALL)
    return enc


class TestCriticalPath:
    def test_slow_drain_names_drain_with_80pct_attribution(self, tmp_path):
        """The acceptance drill: ec.drain armed with a pure delay makes
        every dispatch's drain slow; the analyzer must name `drain` as
        the critical-path stage and attribute >=80% of the wall to it."""
        tr = Tracer(capacity=1 << 14)
        fi.enable("ec.drain", delay=1.0)
        try:
            _staged_encode(tmp_path, tr)  # 12MB -> 2 dispatches
        finally:
            fi.clear()
        report = analyze(tr)
        assert len(report["runs"]) == 1
        run = report["runs"][0]
        assert run["critical_path_stage"] == "drain"
        assert run["attribution"]["drain"]["share"] >= 0.80
        assert run["overlap_efficiency"] <= 0.20
        # a pure delay is slow, not degraded: no retry/fallback evidence
        assert report["degraded"] is False
        # every second of the wall is attributed to a named bucket
        total = sum(v["s"] for v in run["attribution"].values())
        assert abs(total - run["wall_s"]) < 0.05 * run["wall_s"] + 0.01
        # the per-dispatch critical path agrees
        assert run["critical_path"]
        assert all(seg["stage"] == "drain" for seg in run["critical_path"])

    def test_clean_run_is_not_drain_bound(self, tmp_path):
        """A host-bound run (each dispatch's host work outlasts the
        kernel, here by an injected dispatch delay, which degrades
        nothing) is not named drain-bound."""
        tr = Tracer(capacity=1 << 14)
        fi.enable("ec.dispatch", delay=0.15)
        try:
            _staged_encode(tmp_path, tr)
        finally:
            fi.clear()
        run = analyze(tr)["runs"][0]
        # the drain only waits out the last dispatch's kernel
        assert run["critical_path_stage"] != "drain"
        assert run["overlap_efficiency"] > 0.5
        assert run["degraded"] is False

    @pytest.mark.parametrize("point", ["ec.dispatch", "ec.drain"])
    def test_dispatch_fault_sets_degraded(self, tmp_path, point):
        """A forced per-dispatch CPU fallback (an ec.dispatch or ec.drain
        error) leaves pipeline.fallback evidence: the run and report
        flag degraded."""
        tr = Tracer(capacity=1 << 14)
        fi.enable(point, error_rate=1.0, max_hits=1)
        try:
            enc = _staged_encode(tmp_path, tr)
        finally:
            fi.clear()
        assert enc.stats["fallbacks"] >= 1
        report = analyze(tr)
        assert report["degraded"] is True
        run = report["runs"][0]
        assert run["degraded"] is True
        assert run["fallbacks"] >= 1
        assert point.split(".")[1] + "_fault" in run["fallback_reasons"]

    def test_counters_alone_mark_degraded(self, tmp_path):
        """Ring rotation can evict retry spans; the restart/fallback
        counters still force the degraded verdict."""
        tr = Tracer(capacity=1 << 14)
        _staged_encode(tmp_path, tr)
        assert analyze(tr)["degraded"] is False
        report = analyze(tr, counters={"worker_restarts": 2,
                                       "engine_fallbacks": 0})
        assert report["degraded"] is True
        assert report["health"]["worker_restarts"] == 2


    def test_gap_analysis_on_device_encode(self, tmp_path):
        """With a producer slower than the kernel, the fetch track of a
        device encode idles between dispatches while the producer fills,
        dispatches and writes the next one: the gap analysis attributes
        the gaps to those stages."""
        tr = Tracer(capacity=1 << 14)
        fi.enable("ec.dispatch", delay=0.15)  # a slow producer
        try:
            enc = _staged_encode(tmp_path, tr, size_mb=24)
        finally:
            fi.clear()
        run = analyze(tr)["runs"][0]
        ga = run["gap_analysis"]
        assert ga["windows"] >= 2 and ga["busy_s"] > 0
        assert enc.stats["dispatches"] >= 3
        assert ga["classes"]["input_starved"] > 0
        assert ga["classes"]["writer_blocked"] > 0
        # classified seconds never exceed the total gap
        assert sum(ga["classes"].values()) <= ga["gap_total_s"] + 1e-6
        assert "fetch gaps:" in render_report(analyze(tr))


class TestOfflineRoundTrip:
    def test_to_dict_round_trip_equals_live_analysis(self, tmp_path):
        """export -> json -> from_dict -> analyze == live-ring analyze
        (the --trace-out offline contract)."""
        tr = Tracer(capacity=1 << 14)
        fi.enable("ec.drain", delay=0.2)
        try:
            _staged_encode(tmp_path, tr)
        finally:
            fi.clear()
        live = analyze(tr)
        doc = json.loads(json.dumps(tr.to_dict()))
        assert doc["format"] == "seaweedfs-tpu-trace-v1"
        offline = analyze(Tracer.from_dict(doc))
        # also straight from the document, no Tracer reconstruction
        offline2 = analyze(doc)
        for rep in (offline, offline2):
            assert rep["span_count"] == live["span_count"]
            assert len(rep["runs"]) == len(live["runs"])
            for a, b in zip(rep["runs"], live["runs"]):
                assert a["stage_s"] == b["stage_s"]
                assert a["critical_path_stage"] == b["critical_path_stage"]
                assert a["degraded"] == b["degraded"]
                assert a["dispatches"] == b["dispatches"]

    def test_chrome_doc_analysis_matches(self, tmp_path):
        """The Chrome trace-event JSON (bench --trace-out / GET
        /debug/traces) analyzes to the same verdict despite its
        microsecond quantization and relative time base."""
        tr = Tracer(capacity=1 << 14)
        fi.enable("ec.drain", delay=0.3)
        try:
            _staged_encode(tmp_path, tr)
        finally:
            fi.clear()
        live = analyze(tr)["runs"][0]
        chrome = json.loads(json.dumps(tr.to_chrome()))
        run = analyze(chrome)["runs"][0]
        assert run["critical_path_stage"] == live["critical_path_stage"]
        assert run["dispatches"] == live["dispatches"]
        assert abs(run["wall_s"] - live["wall_s"]) < 0.01

    def test_partial_trace_without_root_still_reports(self):
        tr = Tracer()
        with tr.span("pipeline.drain", dispatch=0):
            pass
        report = analyze(tr)
        assert report["runs"] and report["runs"][0].get("partial") is True

    def test_empty_trace(self):
        report = analyze(Tracer())
        assert report["runs"] == [] and report["degraded"] is False
        assert "no pipeline runs" in render_report(report)


class TestSurfaces:
    @pytest.fixture()
    def master(self):
        from seaweedfs_tpu.master.server import MasterServer
        from tests.conftest import free_port

        m = MasterServer(port=free_port()).start()
        try:
            yield m
        finally:
            m.stop()

    def test_analyze_endpoint_and_shell_command(self, master, tmp_path):
        from seaweedfs_tpu.shell import CommandEnv, run_command
        from seaweedfs_tpu.utils.httpd import http_bytes

        tr = enable_tracing()
        tr.clear()
        try:
            fi.enable("ec.drain", delay=0.2)
            try:
                _staged_encode(tmp_path, None)  # global tracer
            finally:
                fi.clear()
            status, body, _ = http_bytes(
                "GET", f"http://{master.url}/debug/traces/analyze")
            assert status == 200
            report = json.loads(body)
            assert report["runs"]
            assert report["runs"][0]["critical_path_stage"] == "drain"
            assert "health" in report  # counters ride along
            # text rendering
            status, text, _ = http_bytes(
                "GET",
                f"http://{master.url}/debug/traces/analyze?format=text")
            assert status == 200 and b"drain-bound" in text
            # shell command against the live server
            env = CommandEnv(master.url)
            out = run_command(env, f"trace.analyze -server {master.url}")
            assert "critical path" in out and "drain" in out
            # shell command against a saved trace file (offline)
            path = str(tmp_path / "trace.json")
            with open(path, "w") as f:
                json.dump(tr.to_chrome(), f)
            out = run_command(env, f"trace.analyze -file {path}")
            assert "drain-bound" in out
            out = run_command(env, f"trace.analyze -file {path} -json")
            assert json.loads(out)["runs"]
        finally:
            disable_tracing()
            tr.clear()

    def test_profile_endpoint_collapsed_format(self, master):
        from seaweedfs_tpu.utils.httpd import http_bytes

        status, body, _ = http_bytes(
            "GET", f"http://{master.url}/debug/profile?seconds=0.3&hz=200")
        assert status == 200
        for line in body.decode().splitlines():
            stack, _, count = line.rpartition(" ")
            assert stack and count.isdigit()

    def test_attribution_summary_empty(self):
        assert attribution_summary({"runs": [], "degraded": True}) == \
            {"degraded": True}
