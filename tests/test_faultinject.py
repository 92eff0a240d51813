"""Fault injection + chaos drills (the framework SURVEY §5 calls for).

Gates: armed fault points actually fire and auto-disarm; a failing local
EC shard read self-heals through reconstruction; a torn disk write rolls
back cleanly and the volume keeps serving; injected network latency is
observable; everything returns to normal after clear().
"""

from __future__ import annotations

import os

import pytest

from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.volume import Volume
from seaweedfs_tpu.utils import faultinject as fi


@pytest.fixture(autouse=True)
def _clean_faults():
    fi.clear()
    yield
    fi.clear()


def test_fault_point_fires_and_auto_disarms(tmp_path):
    v = Volume(str(tmp_path), "", 1)
    try:
        v.write_needle(Needle(cookie=1, id=1, data=b"before"))
        fi.enable("disk.read", error_rate=1.0, max_hits=2)
        with pytest.raises(OSError):
            v.read_needle(1)
        with pytest.raises(OSError):
            v.read_needle(1)
        # max_hits exhausted: reads recover without operator action
        assert v.read_needle(1).data == b"before"
        assert fi.fired("disk.read") == 2
    finally:
        v.close()


def test_torn_write_rolls_back_and_volume_survives(tmp_path):
    v = Volume(str(tmp_path), "", 2)
    try:
        v.write_needle(Needle(cookie=1, id=1, data=b"good"))
        end_before = v.data_size
        fi.enable("disk.write", error_rate=1.0, max_hits=1)
        with pytest.raises(OSError):
            v.write_needle(Needle(cookie=2, id=2, data=b"doomed"))
        # _append_record truncated back: no torn bytes, old data intact
        assert v.data_size == end_before
        assert v.read_needle(1).data == b"good"
        v.write_needle(Needle(cookie=3, id=3, data=b"after"))
        assert v.read_needle(3).data == b"after"
    finally:
        v.close()


def test_ec_degraded_read_self_heals_on_shard_io_error(tmp_path):
    """A local shard pread failing (bad sector) must not fail the read:
    the store reconstructs the interval from the other shards."""
    from seaweedfs_tpu.volume_server.store import Store

    store = Store([str(tmp_path)], max_volume_count=4)
    v = store.add_volume(7)
    payloads = {i: os.urandom(600) for i in range(1, 9)}
    for i, data in payloads.items():
        v.write_needle(Needle(cookie=i, id=i, data=data))
    store.ec_generate(7)
    store.ec_mount(7)
    # every local shard read errors ONCE; reconstruction must kick in
    fi.enable("shard.read", error_rate=1.0, max_hits=1)
    record, _ = store.read_ec_needle(7, 3)
    assert fi.fired("shard.read") == 1
    assert payloads[3] in record  # needle record embeds the data bytes
    store.close()


def test_net_latency_injection():
    import time

    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.utils.httpd import http_json
    from tests.conftest import free_port

    m = MasterServer(port=free_port(), pulse_seconds=0.5).start()
    try:
        http_json("GET", f"http://{m.url}/cluster/status")  # warm conn
        t0 = time.perf_counter()
        http_json("GET", f"http://{m.url}/cluster/status")
        base = time.perf_counter() - t0
        fi.enable("net.request", delay=0.08)
        t0 = time.perf_counter()
        http_json("GET", f"http://{m.url}/cluster/status")
        slow = time.perf_counter() - t0
        assert slow >= base + 0.07
        fi.clear()
        t0 = time.perf_counter()
        http_json("GET", f"http://{m.url}/cluster/status")
        assert time.perf_counter() - t0 < 0.07
    finally:
        fi.clear()
        m.stop()


def test_coord_exec_fault_fails_plan_step():
    """Arming `coord.exec` makes coordinator plan-execution steps fail
    deterministically — the lever the mid-rebuild chaos drills pull.
    The executor surfaces the fault to its caller (a failed move here);
    recovery (re-plan, no-orphan cleanup) is the coordinator's job and
    is drilled in test_pipeline_chaos."""
    from seaweedfs_tpu.ops.coordinator import (ClusterView, Move,
                                               NodeView, PlanExecutor)

    calls = []
    view = ClusterView(
        nodes={"a:1": NodeView("a:1"), "b:1": NodeView("b:1")},
        shards={1: {0: ["a:1"]}})
    ex = PlanExecutor(post_fn=lambda *a: calls.append(a) or {})
    fi.enable("coord.exec", error_rate=1.0, max_hits=1)
    with pytest.raises(OSError):
        ex.execute_move(view, Move(1, 0, "a:1", "b:1"))
    assert fi.fired("coord.exec") == 1
    assert not calls  # the fault fired BEFORE the wire was touched
    # disarmed: the same step now goes through
    ex.execute_move(view, Move(1, 0, "a:1", "b:1"))
    assert calls


def test_coord_plan_fault_is_contained_by_the_loop():
    """Arming `coord.plan` fails a planning cycle; the coordinator loop
    must contain it (surface last_error, keep cycling) instead of
    dying — the next cycle re-plans."""
    import time as _time

    from seaweedfs_tpu.master.topology import Topology
    from seaweedfs_tpu.ops.coordinator import EcCoordinator

    c = EcCoordinator(topo=Topology(), post_fn=lambda *a: {},
                      interval_s=0.05)
    fi.enable("coord.plan", error_rate=1.0, max_hits=1)
    c.start()
    try:
        deadline = _time.time() + 5
        while _time.time() < deadline and not c.status()["cycles"]:
            _time.sleep(0.05)
        st = c.status()
        assert st["cycles"] > 0  # loop survived the injected fault
        assert fi.fired("coord.plan") == 1
    finally:
        fi.clear()
        c.stop()


# --- peer-scoped network fault points (net.delay / net.drop / ---------------
# net.partition): the scenario engine's wire


def test_net_partition_peer_scoping_unit():
    """hit_peer fires only for the armed peer; params-less arming
    covers every peer."""
    fi.enable("net.partition", error_rate=1.0,
              params={"peer": "h1:80"})
    fi.hit_peer("net.partition", "h2:80")  # other peer: no-op
    assert fi.fired("net.partition") == 0
    with pytest.raises(OSError):
        fi.hit_peer("net.partition", "h1:80")
    assert fi.fired("net.partition") == 1
    fi.enable("net.partition", error_rate=1.0)  # unscoped
    with pytest.raises(OSError):
        fi.hit_peer("net.partition", "anyone:1")


def test_net_drop_error_rate_and_max_hits():
    fi.enable("net.drop", error_rate=1.0, max_hits=1,
              params={"peer": "h1:80"})
    with pytest.raises(OSError):
        fi.hit_peer("net.drop", "h1:80")
    fi.hit_peer("net.drop", "h1:80")  # max_hits spent: passes
    assert fi.fired("net.drop") == 1


def test_net_delay_query_counts_without_sleeping():
    """peer_delay returns the armed delay (counting the hit) instead
    of sleeping, so the egress can apply it deadline-aware."""
    fi.enable("net.delay", delay=7.5, params={"peer": "h1:80"})
    assert fi.peer_delay("net.delay", "h2:80") == 0.0
    assert fi.fired("net.delay") == 0
    assert fi.peer_delay("net.delay", "h1:80") == 7.5
    assert fi.fired("net.delay") == 1
    fi.disable("net.delay")
    assert fi.peer_delay("net.delay", "h1:80") == 0.0
