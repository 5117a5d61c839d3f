"""The three readers the live-consensus cell brings, on readings made
up by hand: an attribute summed over spans, the speculation plane's
served share, and the structured kernel's roofline over a slice whose
launches have several lane counts."""

import types

import pytest

from benchmark import ops
from benchmark.layer_metrics import (
    program_span_attr, speculation_share, trace_roofline_slice)
from benchmark.tests.test_program_span_readers import readings
from tendermint_tpu.libs import tracing
from tendermint_tpu.libs.tracing import TRACER

pytestmark = pytest.mark.skipif(
    not hasattr(tracing, "CONSENSUS_VOTE_QUEUE_WAIT"),
    reason="the program has none of the spans these readers read")


def two_heights():
    TRACER.clear()
    for shed in ((0, 3), (0, 0, 5)):
        with TRACER.span(tracing.CONSENSUS_HEIGHT) as height:
            for n in shed:
                TRACER.begin(tracing.CONSENSUS_VOTE_QUEUE_WAIT,
                             parent=height, lanes=100, shed=n).end()
            with TRACER.span(tracing.SPECULATION_RECONCILE):
                pass


def test_attribute_summed_per_height_and_zero_is_a_reading():
    two_heights()
    spec = {"kind": tracing.CONSENSUS_VOTE_QUEUE_WAIT, "attr": "shed",
            "per": tracing.CONSENSUS_HEIGHT}
    value, note = program_span_attr.read(readings(), spec)
    assert value == 4.0 and note["spans"] == 5 and note["shed"] == 8
    assert program_span_attr.read(
        readings(), dict(spec, attr="never_set"))[0] == 0.0
    assert program_span_attr.read(
        readings(), dict(spec, kind="no.such.kind")) is None
    TRACER.clear()
    assert program_span_attr.read(readings(), spec) is None


def test_speculation_share_of_the_lanes_asked():
    two_heights()
    spec = {"asked": tracing.SPECULATION_RECONCILE,
            "lanes": "lastcommit_lanes", "missed": "missed"}
    value, note = speculation_share.read(
        readings({"lastcommit_lanes": 9_000, "missed": 6_000}), spec)
    assert note["lanes_asked"] == 18_000 and value == pytest.approx(66.667,
                                                                    abs=1e-3)
    assert speculation_share.read(
        readings({"lastcommit_lanes": 9_000, "missed": 0}), spec)[0] == 100.0
    assert speculation_share.read(readings({"missed": 1}), spec) is None
    # a count that is wrong reads as wrong: no floor at 0
    assert speculation_share.read(
        readings({"lastcommit_lanes": 9_000, "missed": 27_000}),
        spec)[0] == -50.0


def test_the_arena_s_metrics_read_its_own_launches():
    """The three `arena_*.live` files are parameters of readers the
    benchmark had: the ledger's `speculation` tag and the general
    kernel's module, neither of which the vote metrics read."""
    import json
    import os

    from benchmark.layer_metrics import ledger_lanes, trace_module

    here = os.path.join(os.path.dirname(__file__), "..", "layer_metrics")
    spec = {name: json.load(open(os.path.join(
        here, name + ".live.json"))) for name in (
        "arena_lanes_per_launch", "arena_launch_exec_ms",
        "arena_kernel_ms", "kernel_ms", "vote_lanes_per_launch")}
    recs = [{"workload": "votes", "kernel": "structured", "lanes": 1_000},
            {"workload": "speculation", "kernel": "resident", "lanes": 5_000},
            {"workload": "speculation", "kernel": "resident", "lanes": 7_000}]
    r = types.SimpleNamespace(
        counters={},
        trace={"modules": {"jit_skernel(1)": {"count": 2, "total_s": 0.008},
                           "jit_kernel(9)": {"count": 3, "total_s": 0.24}}},
        ledger_for=lambda workload, kernel: [
            x for x in recs if x["workload"] == workload])
    assert spec["arena_launch_exec_ms"]["params"]["workload"] == "speculation"
    assert ledger_lanes.read(
        r, spec["arena_lanes_per_launch"]["params"])[0] == 6_000
    assert ledger_lanes.read(
        r, spec["vote_lanes_per_launch"]["params"])[0] == 1_000
    assert trace_module.read(
        r, spec["arena_kernel_ms"]["params"])[0] == pytest.approx(80.0)
    assert trace_module.read(
        r, spec["kernel_ms"]["params"])[0] == pytest.approx(4.0)


def test_roofline_over_a_slice_of_several_lane_counts():
    kind = "TPU v5 lite"
    recs = [{"workload": w, "kernel": "structured", "lanes": n, "mono": t}
            for w, n, t in (("votes", 2_000, 9.0), ("votes", 1_100, 10.5),
                            ("consensus", 3_300, 11.0),
                            ("votes", 900, 11.5), ("votes", 400, 12.0))]
    recs.append({"workload": "speculation", "kernel": "resident",
                 "lanes": 1_000, "mono": 10.7})
    r = types.SimpleNamespace(
        device_kind=kind, counters={"trace_slice_from_mono": 10.0},
        trace={"modules": {"jit_skernel(1)": {"count": 2, "total_s": 0.02},
                           "jit_skernel(2)": {"count": 1, "total_s": 0.01},
                           "jit_kernel": {"count": 9, "total_s": 1.0}}},
        ledger_for=lambda workload, kernel: [
            x for x in recs if kernel in (None, x["kernel"])])
    value, note = trace_roofline_slice.read(
        r, {"pattern": "skernel", "kernel": "structured",
            "msg_bytes": "msg_bytes"})
    # three executions: the three launches from where the slice begins
    assert note["lanes_of_each"] == [1_100, 3_300, 900]
    assert note["lanes_from"] == "slice" and note["kernel_s"] == 0.03
    least = ops.roofline(kind, 5_300, 0)["least_s"]
    assert value == pytest.approx(100.0 * least / 0.03)
    # no counter: the window's mean lanes stand in, and the note says so
    r.counters = {}
    _, note = trace_roofline_slice.read(
        r, {"pattern": "skernel", "kernel": "structured",
            "msg_bytes": "msg_bytes"})
    assert note["lanes_from"] == "window"
    assert note["lanes"] == pytest.approx(3 * 7_700 / 5)
