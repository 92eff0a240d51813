"""What the per-layer readers (metrics/<name>.py) share.

A reader gets the run (``run.RunData``): the program's spans of the
window (Chrome trace events: ``ts``/``dur`` in microseconds, ``args``
with ``span_id``/``parent_id``), the device trace's reduction, the
``/status`` documents before and after the window, the mix with its
operations, and the chip's peaks.  A reader that finds nothing to read
returns None, and the metric is left out of the result line.
"""

from __future__ import annotations

import json
import os

from kernels import rs_bytes

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(kind: str) -> dict:
    """The chip's peaks by the device_kind JAX reports.  An unknown kind
    is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["kinds"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(known: {sorted(table)})")
    return table[kind]


def named(spans: list[dict], name: str) -> list[dict]:
    return [s for s in spans if s["name"] == name]


def span_share(spans: list[dict], part: str, whole: str) -> float | None:
    """Summed time of `part` spans over summed time of `whole`, in %."""
    w = sum(s["dur"] for s in named(spans, whole))
    if not w:
        return None
    return 100.0 * sum(s["dur"] for s in named(spans, part)) / w


def mean_ms(spans: list[dict], name: str) -> float | None:
    d = [s["dur"] for s in named(spans, name)]
    return sum(d) / len(d) / 1e3 if d else None


def self_ms(spans: list[dict], suffix: str) -> float | None:
    """Mean self time of the spans whose name ends in `suffix`: each
    span's duration less the part its child spans cover."""
    kids: dict[str, list[tuple[float, float]]] = {}
    for s in spans:
        p = s.get("args", {}).get("parent_id")
        if p:
            kids.setdefault(p, []).append((s["ts"], s["ts"] + s["dur"]))
    selfs = []
    for s in spans:
        if not s["name"].endswith(suffix):
            continue
        lo, hi = s["ts"], s["ts"] + s["dur"]
        covered, end = 0.0, lo
        for a, b in sorted(kids.get(s.get("args", {}).get("span_id"), [])):
            a, b = max(a, end), min(b, hi)
            if b > a:
                covered += b - a
                end = b
        selfs.append(s["dur"] - covered)
    return sum(selfs) / len(selfs) / 1e3 if selfs else None


def idle_share(run) -> float | None:
    if run.device is None or not run.device.devices:
        return None
    return 100.0 * (1.0 - run.device.mean_busy_s / run.window_s)


def roofline_share(run, hbm_bytes: int, device_s: float) -> float | None:
    """Least time the work needs at the chip's HBM bandwidth over the
    measured device time, summed over devices, in %."""
    if not hbm_bytes or device_s <= 0:
        return None
    return 100.0 * hbm_bytes / run.peaks["hbm_bytes_per_s"] / device_s


def kernel_share(run, hbm_bytes: int, kernel: str = "gf_matmul"
                 ) -> float | None:
    """The kernel's ops alone (kernels.KERNELS)."""
    if run.device is None:
        return None
    return roofline_share(run, hbm_bytes, run.device.kernel_s.get(kernel, 0))


def program_share(run, hbm_bytes: int, program: str = "gf_dispatch"
                  ) -> float | None:
    """The whole jitted dispatch around the kernel (kernels.PROGRAMS)."""
    if run.device is None:
        return None
    return roofline_share(run, hbm_bytes,
                          run.device.program_s.get(program, 0))


def ops_bytes(run) -> int:
    """HBM bytes of the window's encodes or rebuilds."""
    m = run.mix
    r = 4 if m.op == "ec_generate" else len(m.lost)
    return m.result["ops"] * rs_bytes(10, r, m.shard_size)


def reconstruct_bytes(run) -> int:
    """HBM bytes of the window's reconstructs: each ec.reconstruct span
    says how many survivor bytes it read (10 columns) and how many
    shards it made."""
    total = 0
    for s in named(run.spans, "ec.reconstruct"):
        a = s.get("args", {})
        k = int(a.get("k", 10))
        total += rs_bytes(k, int(a.get("missing", 1)),
                          int(a.get("bytes", 0)) // k)
    return total
