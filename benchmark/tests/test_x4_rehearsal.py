"""A whole run of seal.vol1g.x4, rehearsed on four forced CPU devices
(XLA_FLAGS passes through run.py to the volume server): the per-device
dispatch queues spread every encode over the four, and `correct` must
be true on the sound path and false when the encode leaves the disk as
it was (stale), changes one parity byte where it is made (alter), or
falls back to the CPU codec (fallback).  test_faults.py holds the
one-chip cells."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
RUN = os.path.join(os.path.dirname(HERE), "run.py")


def rehearse(fault, seed: int = 2 ** 31 + 91) -> dict:
    argv = [sys.executable, RUN, "--workload", "seal.vol1g.x4", "--seed",
            str(seed), "--seconds", "2", "--trace", "0"]
    if fault:
        argv += ["--fault", fault]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(argv, env=env, capture_output=True, text=True,
                       timeout=300)
    lines = [ln for ln in p.stdout.splitlines()
             if ln.startswith("rehearsal: ")]
    assert p.returncode == 3 and lines, p.stderr[-3000:]
    return json.loads(lines[-1][len("rehearsal: "):])


@pytest.mark.parametrize("fault", [None, "stale", "alter", "fallback"])
def test_x4_correct_only_on_the_sound_path(fault):
    r = rehearse(fault)
    assert r["device"]["count"] == 4
    assert r["correct"] is (fault is None), r["checks"]
    assert r["attempted"] > 0
