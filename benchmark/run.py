#!/usr/bin/env python3
"""The benchmark of the EC volume server: one cell, one run.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

It finds the cell in ``BENCHMARK.json``, its configuration under
``benchmark/configs/``, its traffic mix under ``benchmark/traffic/``
and its per-layer readers under ``benchmark/metrics/``, by name.  Then:
build the native libraries if they are missing or stale, make the
volume from the seed, start ``weed.py master`` and the volume server
(under ``launch_volume.py``, which holds the chip), set up and warm up
the mix, measure for ``--seconds``, stop the servers, compare every
answer with the plain reference, and print one JSON line last.

This process never imports JAX.  The device it reports is the one the
volume server computes on (``/status`` ``EcDevice``).  A run that finds
no TPU, or not as many chips as the cell asks for, fails and prints no
result.  ``JAX_PLATFORMS=cpu`` makes a rehearsal: tiny sizes, the line
printed as ``rehearsal: {...}``, no device metric, exit code 3.

With ``--trace 1`` the program's spans are on, the chip is traced over
the window, and the line holds the per-layer metrics; with ``--trace
0`` it holds the end-to-end ones.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
from dataclasses import dataclass, field

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import drive  # noqa: E402
import needles  # noqa: E402
import trace_reduce  # noqa: E402
from cluster import Cluster  # noqa: E402
from kernels import KERNELS, PROGRAMS  # noqa: E402
from layers import peaks  # noqa: E402
from reference import rs  # noqa: E402

CACHE = os.path.join(HERE, ".cache", "jax")


class RunFailed(Exception):
    pass


@dataclass
class RunData:
    """What the per-layer readers read (layers.py)."""
    mix: drive.Mix
    window_s: float
    spans: list = field(default_factory=list)
    device: trace_reduce.Reduction | None = None
    status_before: dict = field(default_factory=dict)
    status_after: dict = field(default_factory=dict)
    peaks: dict | None = None


def info(msg: str) -> None:
    print(f"info: {msg}", file=sys.stderr, flush=True)


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise RunFailed(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return bench, cell, cfg, traffic


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def per_layer(bench: dict, cell: str) -> list[dict]:
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else applies(e2e[m["moves"]], cell))]


def reader(metric: dict):
    path = os.path.join(HERE, "metrics", metric["name"] + ".py")
    spec = importlib.util.spec_from_file_location(
        "metric_" + metric["name"].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if (mod.LAYER, mod.MOVES) != (metric["layer"], metric["moves"]):
        raise RunFailed(f"{path} says layer {mod.LAYER!r} moves "
                        f"{mod.MOVES!r}; BENCHMARK.json says "
                        f"{metric['layer']!r} moves {metric['moves']!r}")
    return mod.read


def build_native() -> None:
    """make rebuilds a library only where it is missing or older than
    its source."""
    subprocess.run(["make", "-s", "-j4", "-C",
                    os.path.join(ROOT, "seaweedfs_tpu", "native")],
                   check=True, stdout=subprocess.DEVNULL)


def child_env(data_dir: str) -> dict:
    env = dict(os.environ)
    # a fixed directory inside the checkout, whatever the machine sets:
    # the path is part of the cache's key, and the two sides of a check
    # share nothing.  JAX's default 1 s floor would keep the small
    # kernels out of it (PR 21), so every compile is kept.
    env["JAX_COMPILATION_CACHE_DIR"] = CACHE
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["TPU_LOG_DIR"] = os.path.join(data_dir, "tpu_logs")
    env["TMPDIR"] = data_dir
    return env


def run(args) -> tuple[dict, list[drive.Check], bool]:
    t_start = time.monotonic()
    bench, cell, cfg, traffic = load_cell(args.workload)
    geometry = (cfg["data_shards"], cfg["parity_shards"],
                cfg["large_block_bytes"], cfg["small_block_bytes"])
    if geometry != (rs.K, rs.M, rs.LARGE_BLOCK, rs.SMALL_BLOCK):
        raise RunFailed(f"{cfg['name']} asks for {geometry}; the volume "
                        f"server and the reference fix RS(10,4) with "
                        f"1 GiB and 1 MiB blocks")
    rehearsal = os.environ.get("JAX_PLATFORMS", "").strip() == "cpu"
    if rehearsal:
        cfg = {**cfg, **cfg["rehearsal"]}
        info(f"REHEARSAL pinned to the CPU at tiny size "
             f"{cfg['rehearsal']}: not a measurement")
    build_native()
    os.makedirs(CACHE, exist_ok=True)
    data_dir = tempfile.mkdtemp(prefix="bench-")
    cl = Cluster(data_dir, child_env(data_dir))
    try:
        vol = needles.make(cfg, args.seed)
        dat = needles.write(vol, cl.vdir)
        info(f"volume {vol.dat_size} bytes, {len(vol)} needles, made in "
             f"{time.monotonic() - t_start:.3f} s")
        if traffic["op"] == "get":
            dat = None  # reads are checked against the needles' bytes
        where = {"ec_generate": "encode", "ec_rebuild": "rebuild",
                 "get": "reconstruct"}[traffic["op"]]
        cl.start(spans=bool(args.trace),
                 fault=args.fault and f"{where}.{args.fault}")
        dev = cl.status()["EcDevice"]
        device = {"platform": dev["platform"], "kind": dev["kind"],
                  "count": dev["count"]}
        if not rehearsal and (dev["platform"] != "tpu"
                              or dev["count"] != cell["chips"]):
            raise RunFailed(f"the cell asks for {cell['chips']} TPU "
                            f"chip(s); the volume server found {device}")
        mix = drive.Mix(traffic, vol, dat, cl, args.seed)
        mix.setup()
        mix.warmup()
        data = RunData(mix, 0.0)
        marks: dict = {}

        def on_start() -> None:
            marks["setup_s"] = time.monotonic() - t_start
            data.status_before = cl.status()
            if args.trace:
                cl.spans()  # drain set-up's spans
                if not rehearsal:
                    cl.start_trace()

        mix.window(args.seconds, on_start)
        data.window_s = mix.result["window_s"]
        profile = None
        if args.trace:
            if not rehearsal:
                data.window_s, profile = cl.stop_trace()
            data.spans = cl.spans()
        data.status_after = cl.status()
        if not rehearsal:
            device["memory_peak_bytes"] = max(
                data.status_after["EcDevice"]["peak_bytes_in_use"])
        # a dispatch that fell back to the CPU codec answers the same
        # bytes, but it is not the device path the run reports
        device["engine_fallbacks"] = (
            data.status_after["EcPipeline"]["engine_fallbacks"]
            - data.status_before["EcPipeline"]["engine_fallbacks"])
    except Exception:
        cl.stop()
        print(cl.log_tails(), file=sys.stderr)
        shutil.rmtree(data_dir, ignore_errors=True)
        raise
    cl.stop()
    try:
        info(f"window {mix.result['window_s']:.3f} s, {mix.attempted} "
             f"operations, {mix.failed} failed; {json.dumps(mix.result)}")
        for e in mix.errors:
            info(f"error: {e}")
        t = time.monotonic()
        checks = mix.check() + [drive.Check(
            "engine_fallbacks", device["engine_fallbacks"], 0)]
        info(f"reference check {time.monotonic() - t:.3f} s")
        if profile:
            path = trace_reduce.find_trace(profile)
            if path is None:
                raise RunFailed(f"no perfetto trace under {profile}")
            data.device = trace_reduce.reduce(trace_reduce.load(path),
                                              KERNELS, PROGRAMS)
            data.peaks = peaks(device["kind"])
            if data.device.devices != device["count"] \
                    or data.device.mean_busy_s <= 0:
                raise RunFailed(f"the trace shows {data.device.devices} "
                                f"device(s), busy {data.device.busy_s} s")
            info(f"trace: busy {data.device.busy_s} s of "
                 f"{data.window_s} s, kernels {data.device.kernel_s} "
                 f"({data.device.kernel_events} events), programs "
                 f"{data.device.program_s} "
                 f"({data.device.program_events} events)")
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)

    metrics = {}
    if args.trace:
        for m in per_layer(bench, cell["name"]):
            v = reader(m)(data)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in bench["end_to_end"]:
            if applies(m, cell["name"]):
                v = marks["setup_s"] if m["name"] == "setup_s" \
                    else mix.result[m["name"]]
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {"correct": all(c.ok for c in checks),
              "attempted": mix.attempted, "failed": mix.failed,
              "metrics": metrics, "device": device}
    if data.device is not None:
        device["busy_s"] = data.device.mean_busy_s
        device["window_s"] = data.window_s
        result["breakdown"] = {"device_ops": data.device.top_ops,
                               "idle_gaps": data.device.gaps}
    return result, checks, rehearsal


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=[0, 1], default=0)
    # the benchmark's tests and control runs only: a fault planted under
    # the timed path (launch_volume.py), which `correct` must catch
    p.add_argument("--fault",
                   choices=["stale", "half", "alter", "fallback"],
                   help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    try:
        result, checks, rehearsal = run(args)
    except Exception as e:  # noqa: BLE001 - a failed run prints no result
        print(f"run.py: FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    for c in checks:
        print(f"check {c.name}: {c.value} (limit {c.limit})"
              f"{'' if c.ok else ' FAILED'}", file=sys.stderr)
    if "jax" in sys.modules:
        print("run.py: FAILED: the parent imported jax", file=sys.stderr)
        return 1
    line = json.dumps(result)
    if rehearsal:
        print(f"rehearsal: {line}", flush=True)
        return 3
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
