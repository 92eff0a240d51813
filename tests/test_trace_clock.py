"""The program's spans on the device trace's clock.

Once a device engine has set up (ops/device.py), every span the tracer
opens also enters a jax.profiler annotation carrying its span_id, so a
profiler trace of the process shows each span on its /host:CPU plane,
on the profiler's timeline, joinable to /debug/traces by id.  A
disabled tracer never calls the hook, and the observability package
never imports JAX.
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import subprocess
import sys

import numpy as np

from seaweedfs_tpu.observability import Tracer, get_tracer
from seaweedfs_tpu.observability import tracer as tracer_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _host_events(profile_dir: str) -> dict[str, dict]:
    """{span_id: event} of the annotations on the /host:CPU plane of
    the profiler's perfetto trace."""
    path = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "perfetto_trace.json.gz")))[-1]
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    host = {e["pid"] for e in events
            if e.get("ph") == "M" and e.get("name") == "process_name"
            and (e.get("args") or {}).get("name") == "/host:CPU"}
    return {e["args"]["span_id"]: e for e in events
            if e.get("ph") == "X" and e.get("pid") in host
            and "span_id" in (e.get("args") or {})}


def _clock_faults(tracer, profile_dir) -> list:
    """Every pipeline span against its annotation: the same name,
    durations within max(2%, 0.2 ms), starts relative to the first span
    within 1 ms.  Returns what disagrees."""
    by_id = _host_events(profile_dir)
    spans = [s for s in tracer.snapshot() if s.name.startswith("pipeline.")]
    assert {"pipeline.encode_file", "pipeline.fill", "pipeline.dispatch",
            "pipeline.drain"} <= {s.name for s in spans}
    missing = [(s.name, s.span_id) for s in spans if s.span_id not in by_id]
    assert not missing
    t_first = min(s.t0 for s in spans)
    ts_first = min(float(by_id[s.span_id]["ts"]) for s in spans)
    faults = []
    for s in spans:
        e = by_id[s.span_id]
        assert e["name"] == s.name
        dur_us = s.duration * 1e6
        start_us = (s.t0 - t_first) * 1e6
        if abs(float(e["dur"]) - dur_us) > max(0.02 * dur_us, 200.0) \
                or abs(float(e["ts"]) - ts_first - start_us) > 1000.0:
            faults.append((s.name, e["ts"], e["dur"], start_us, dur_us))
    return faults


def test_pipeline_spans_land_on_the_profilers_host_plane(tmp_path):
    """A thread preempted between a span's own clock read and its
    annotation's (a few bytecodes apart) puts the two a scheduler slice
    apart; on a loaded host that happens to a run now and then, so the
    traced encode gets three tries."""
    import jax

    from seaweedfs_tpu.ec.streaming import StreamingEncoder

    dat = tmp_path / "v.dat"
    dat.write_bytes(np.random.default_rng(23).integers(
        0, 256, 300_000, dtype=np.uint8).tobytes())
    tracer = Tracer(capacity=1 << 14)
    # the device engine's set-up installs the annotation hook
    enc = StreamingEncoder(10, 4, engine="device", tracer=tracer)
    enc.dispatch_b = 8192
    kw = dict(large_block_size=10_000, small_block_size=100)
    enc.encode_file(str(dat), str(tmp_path / "warm"), **kw)  # compiles
    for attempt in range(3):
        tracer.clear()
        prof = str(tmp_path / f"prof{attempt}")
        jax.profiler.start_trace(prof, create_perfetto_trace=True)
        try:
            enc.encode_file(str(dat), str(tmp_path / "v"), **kw)
        finally:
            jax.profiler.stop_trace()
        faults = _clock_faults(tracer, prof)
        if not faults:
            break
    assert not faults


def test_disabled_tracer_never_calls_the_hook(monkeypatch):
    calls: list[str] = []

    def hook(name, **args):
        calls.append(name)
        assert set(args) == {"span_id"}
        return contextlib.nullcontext()

    monkeypatch.setattr(tracer_mod, "annotation_hook", hook)
    tr = Tracer(enabled=False)
    with tr.span("off", dispatch=1):
        pass
    assert calls == []
    tr.enabled = True
    with tr.span("on") as sp:
        assert calls == ["on"]
    # spans recorded after the fact cannot be annotated
    tr.add_span("after", 0.0, 1.0)
    tr.event("instant")
    assert calls == ["on"]
    assert [s.span_id for s in tr.snapshot() if s.name == "on"] == [sp.span_id]


def test_observability_never_imports_jax():
    code = ("import sys, seaweedfs_tpu.observability; "
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]


def test_compile_is_a_span_under_the_step_that_compiled(monkeypatch):
    import jax
    import jax.numpy as jnp

    from seaweedfs_tpu.ops import device

    device.ensure_compile_cache()
    tr = get_tracer()
    monkeypatch.setattr(tr, "enabled", True)
    before = device.device_doc()["compile_requests"]
    with tr.span("step") as step:
        jax.jit(lambda x: x * 11 + 5)(jnp.arange(9)).block_until_ready()
    compiles = [s for s in tr.snapshot() if s.name == "device.compile"
                and s.parent_id == step.span_id]
    assert compiles and all(s.duration > 0 for s in compiles)
    # the count stays where it was: every compile span is a request
    assert device.device_doc()["compile_requests"] - before >= len(compiles)
