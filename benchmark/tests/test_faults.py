"""A whole run of each cell, rehearsed on the CPU (JAX_PLATFORMS=cpu
skips the look for a chip and sizes the cell down), with the timed path
sound and then broken underneath (launch_volume.py --fault): `correct`
must be true on the sound path and false under every fault the cell
can have.  ``alter`` is also each cell's control (PERF.md): one byte of
an answer wrong where it is made.

- stale: the encode or rebuild returns and leaves the disk as it was;
- half: the back half of the columns (or of a reconstructed interval)
  left out, zeros in their place;
- alter: one byte of a parity shard, a rebuilt shard or a reconstructed
  interval changed where it is produced;
- fallback: every device dispatch of an encode or a reconstruct fails,
  and the program's CPU codec answers the same bytes off the device
  (the rebuild has no such fallback: it fails).

The exchange between chips does not exist in these one-chip cells.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
RUN = os.path.join(os.path.dirname(HERE), "run.py")

CASES = [
    ("seal.vol1g", None), ("seal.vol1g", "stale"), ("seal.vol1g", "half"),
    ("seal.vol1g", "alter"), ("seal.vol1g", "fallback"),
    ("repair.vol1g", None), ("repair.vol1g", "stale"),
    ("repair.vol1g", "half"), ("repair.vol1g", "alter"),
    ("degraded_read.bench1k", None), ("degraded_read.bench1k", "half"),
    ("degraded_read.bench1k", "alter"),
    ("degraded_read.bench1k", "fallback"),
]


def rehearse(cell: str, fault, seed: int = 2 ** 31 + 77) -> dict:
    argv = [sys.executable, RUN, "--workload", cell, "--seed", str(seed),
            "--seconds", "2", "--trace", "0"]
    if fault:
        argv += ["--fault", fault]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(argv, env=env, capture_output=True, text=True,
                       timeout=300)
    lines = [ln for ln in p.stdout.splitlines()
             if ln.startswith("rehearsal: ")]
    assert p.returncode == 3 and lines, p.stderr[-3000:]
    return json.loads(lines[-1][len("rehearsal: "):])


@pytest.mark.parametrize("cell,fault", CASES)
def test_correct_only_on_the_sound_path(cell, fault):
    r = rehearse(cell, fault)
    assert r["correct"] is (fault is None), r["checks"]
    # the checks are printed last, each number beside its limit
    assert list(r)[-1] == "checks"
    assert r["attempted"] > 0
