"""Production-shaped scenario harness (ROADMAP open item 4).

Uniform-random RPS loops are nothing like millions of users.  This
package drives a spawned cluster with the
shapes production actually has — Zipfian object popularity over a hot
set, a mixed size distribution, delete churn, and MID-LOAD fault
injection from the W701-checked FAULT_POINTS registry — with the
graceful-degradation plane (deadlines, retry budgets, admission
control) and the alert engine live, and emits per-route RED
measurements, per-phase p99s, shed/retry/deadline counters, the alert
timeline, a sampled stitched trace, and a degraded VERDICT against the
spec's expectations.

    from seaweedfs_tpu.scenarios import default_scenarios, run_scenario
    for spec in default_scenarios():
        result = run_scenario(spec)

default_scenarios() holds the three canonical specs (Zipfian hot-set
read storm, mixed-size write+churn, failure-under-load).

Two more entry points close the loop with the observability stack:

  - replay.spec_from_recording fits a workload recording
    (observability/reqlog.py, `weed shell workload.export`) into a
    replayable spec — recorded production traffic becomes a
    repeatable scenario, open-loop paced at recorded (or -speed
    scaled) rate, with replay_fidelity machine-checking the
    reproduction;
  - capacity.find_capacity / probe_cluster binary-search the max
    sustainable rps per route class under a declared SLO.
"""

from .capacity import CapacitySLO, find_capacity, measure_rate
from .engine import run_against, run_scenario
from .failover import run_failover
from .replay import (recording_profile, replay_fidelity,
                     spec_from_recording)
from .spec import (FaultSpec, ScenarioSpec, default_scenarios,
                   failure_under_load, flash_crowd,
                   flash_crowd_autoscale, master_failover,
                   read_storm, write_churn)
from .workload import SizeSampler, ZipfSampler

__all__ = [
    "FaultSpec", "ScenarioSpec", "default_scenarios", "run_scenario",
    "run_against", "run_failover",
    "read_storm", "write_churn", "failure_under_load", "flash_crowd",
    "flash_crowd_autoscale", "master_failover",
    "ZipfSampler", "SizeSampler",
    "spec_from_recording", "recording_profile", "replay_fidelity",
    "CapacitySLO", "find_capacity", "measure_rate",
]
