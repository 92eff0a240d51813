"""A whole run of degraded_read.vol4g, rehearsed on the CPU at its
16 MiB rehearsal size (JAX_PLATFORMS=cpu): mixed 1 KiB-4 MiB needles
read with shard 3 lost.  `correct` must be true on the sound path and
false when a reconstructed interval has one byte changed where it is
made (alter), its back half left out (half), or the device engine fails
and the CPU codec answers (fallback).  test_faults.py holds the other
one-chip cells."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
RUN = os.path.join(os.path.dirname(HERE), "run.py")


def rehearse(fault, seed: int = 2 ** 31 + 97) -> dict:
    argv = [sys.executable, RUN, "--workload", "degraded_read.vol4g",
            "--seed", str(seed), "--seconds", "2", "--trace", "0"]
    if fault:
        argv += ["--fault", fault]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(argv, env=env, capture_output=True, text=True,
                       timeout=300)
    lines = [ln for ln in p.stdout.splitlines()
             if ln.startswith("rehearsal: ")]
    assert p.returncode == 3 and lines, p.stderr[-3000:]
    return json.loads(lines[-1][len("rehearsal: "):])


@pytest.mark.parametrize("fault", [None, "alter", "half", "fallback"])
def test_vol4g_correct_only_on_the_sound_path(fault):
    r = rehearse(fault)
    assert r["correct"] is (fault is None), r["checks"]
    assert r["attempted"] > 0
    assert set(r["metrics"]) == {"read_p99_ms", "read_ops_s", "setup_s"}
