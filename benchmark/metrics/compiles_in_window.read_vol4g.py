"""Compiles inside the window of mixed-width degraded reads:
EcDevice.compiles (ops/device.py, in /status) after the window less
before.  A persistent-cache hit counts as none."""

LAYER = "device"
MOVES = "read_p99_ms"


def read(run):
    before = run.status_before.get("EcDevice", {}).get("compiles")
    after = run.status_after.get("EcDevice", {}).get("compiles")
    if before is None or after is None:
        return None
    return after - before
