"""The trace reduction on a recorded trace: two encode dispatches of
seal.vol1g on one v5e (my chip run, PR 22), cut down to the device
plane's events and a few host events."""

import os

import pytest

import trace_reduce
from kernels import KERNELS, PROGRAMS

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "seal_two_dispatches.json.gz")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(trace_reduce.load(FIXTURE), KERNELS,
                               PROGRAMS)


def test_one_device_and_its_busy_union(reduced):
    # 46 ops on /device:TPU:0 "XLA Ops"; the "XLA Modules" line and the
    # host plane do not count
    assert reduced.devices == 1
    assert reduced.busy_s == [pytest.approx(0.25631392617, rel=1e-9)]


def test_gf_kernel_time(reduced):
    assert reduced.kernel_events == {"gf_matmul": 2}
    assert reduced.kernel_s["gf_matmul"] == pytest.approx(0.002011026094,
                                                          rel=1e-9)


def test_gf_dispatch_time(reduced):
    # the "XLA Modules" line: two runs of jit_gf_matmul_pallas_packed,
    # the kernel with the lane packing around it, which is all the
    # device did
    assert reduced.program_events == {"gf_dispatch": 2}
    assert reduced.program_s["gf_dispatch"] == pytest.approx(
        0.256313973828, rel=1e-9)


def test_breakdown(reduced):
    # the u32 lane packing (fusion, fusion.1..3) outweighs the kernel
    assert reduced.top_ops[0][0] == "fusion.1"
    assert len(reduced.top_ops) == 10 and len(reduced.gaps) == 10
    assert reduced.gaps[0] == ["or_bitcast_fusion -> fusion.6",
                               pytest.approx(0.022587374922, rel=1e-6)]


def test_union_merges_overlaps():
    assert trace_reduce.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == \
        [(0, 4), (5, 6)]
