"""From the profiler's trace of the process that holds the chip to numbers.

``jax.profiler`` (started by ``launch_volume.py`` with
``create_perfetto_trace=True``) writes ``perfetto_trace.json.gz`` under
``<dir>/plugins/profile/<time>/``: Chrome trace events, ``ts`` and
``dur`` in microseconds, one process per plane.  A device plane is a
process named ``/device:TPU:<n>``; its ops run on its thread named
``XLA Ops``.  This module reads that file with ``gzip`` and ``json``
alone, in the parent, after the trace has stopped.

- busy: the union of the op intervals on each device, in seconds;
- kernel time: the summed durations of the ops whose name matches a
  kernel's pattern (``kernels.py``);
- program time: the summed durations of the runs of a jitted program,
  on the plane's ``XLA Modules`` line, whose module name matches a
  program's pattern (``kernels.py``): a kernel with all the ops around
  it in one dispatch;
- the ops that took most time, and the longest idle gaps, each gap
  named by the ops on either side of it.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE = re.compile(r"^/device:TPU:(\d+)")


@dataclass
class Reduction:
    devices: int = 0
    busy_s: list[float] = field(default_factory=list)   # per device
    kernel_s: dict[str, float] = field(default_factory=dict)
    kernel_events: dict[str, int] = field(default_factory=dict)
    program_s: dict[str, float] = field(default_factory=dict)
    program_events: dict[str, int] = field(default_factory=dict)
    top_ops: list[list] = field(default_factory=list)   # [name, seconds]
    gaps: list[list] = field(default_factory=list)      # [name, seconds]

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s) if self.busy_s else 0.0


def find_trace(profile_dir: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "perfetto_trace.json.gz")))
    return hits[-1] if hits else None


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def device_line(doc: dict, line: str) -> dict[int, list[dict]]:
    """{device index: its events on `line`}, from the device planes."""
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    dev_of_pid: dict[int, int] = {}
    line_tid: dict[int, set] = {}
    for e in events:
        if e.get("ph") != "M":
            continue
        name = (e.get("args") or {}).get("name", "")
        if e.get("name") == "process_name":
            m = DEVICE.match(name)
            if m:
                dev_of_pid[e["pid"]] = int(m.group(1))
        elif e.get("name") == "thread_name" and name == line:
            line_tid.setdefault(e["pid"], set()).add(e.get("tid"))
    out: dict[int, list[dict]] = {d: [] for d in dev_of_pid.values()}
    for e in events:
        pid = e.get("pid")
        if e.get("ph") == "X" and pid in dev_of_pid \
                and e.get("tid") in line_tid.get(pid, ()):
            out[dev_of_pid[pid]].append(e)
    return out


def reduce(doc: dict, kernels: dict[str, re.Pattern],
           programs: dict[str, re.Pattern], top: int = 10) -> Reduction:
    per_dev = device_line(doc, OPS_LINE)
    r = Reduction(devices=len(per_dev))
    for mods in device_line(doc, MODULES_LINE).values():
        for e in mods:
            for k, pat in programs.items():
                if pat.search(e["name"]):
                    r.program_s[k] = r.program_s.get(k, 0.0) \
                        + float(e.get("dur", 0)) / 1e6
                    r.program_events[k] = r.program_events.get(k, 0) + 1
    by_name: dict[str, float] = {}
    gaps: list[tuple[float, str]] = []
    for ops in per_dev.values():
        spans = [(float(e["ts"]), float(e["ts"]) + float(e.get("dur", 0)))
                 for e in ops]
        busy = union(spans)
        r.busy_s.append(sum(hi - lo for lo, hi in busy) / 1e6)
        for e in ops:
            d = float(e.get("dur", 0)) / 1e6
            by_name[e["name"]] = by_name.get(e["name"], 0.0) + d
            for k, pat in kernels.items():
                if pat.search(e["name"]):
                    r.kernel_s[k] = r.kernel_s.get(k, 0.0) + d
                    r.kernel_events[k] = r.kernel_events.get(k, 0) + 1
        ordered = sorted(ops, key=lambda e: float(e["ts"]))
        end, last = None, None  # the running end of busy, and its op
        for e in ordered:
            lo = float(e["ts"])
            if end is not None and lo > end:
                gaps.append(((lo - end) / 1e6, f"{last} -> {e['name']}"))
            hi = lo + float(e.get("dur", 0))
            if end is None or hi >= end:
                end, last = hi, e["name"]
    r.top_ops = [[n, s] for n, s in sorted(by_name.items(),
                                           key=lambda x: -x[1])[:top]]
    r.gaps = [[n, s] for s, n in sorted(gaps, key=lambda x: -x[0])[:top]]
    return r
