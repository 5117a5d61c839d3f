"""The readers of the program's own spans and of the kernels' phases,
on readings made up by hand (and, for the phases, on the small trace
recorded on the chip)."""

import os
import shutil
import time
import types

import pytest

from benchmark.layer_metrics import (
    program_span_self, program_span_stat, trace_scope)
from tendermint_tpu.libs import tracing
from tendermint_tpu.libs.tracing import TRACER

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

pytestmark = pytest.mark.skipif(
    not hasattr(tracing, "DB_WRITE"),
    reason="the program has none of the spans these readers read")


def readings(counters=None, **extra):
    """What harness.Readings would hold of the global ring now."""
    return types.SimpleNamespace(
        program_spans=[(r[0], r[4], r[5]) for r in TRACER.snapshot()],
        counters=counters or {}, **extra)


def spin(ms: float) -> None:
    end = time.perf_counter() + ms / 1e3
    while time.perf_counter() < end:
        pass


def two_blocks():
    """Two state.apply_block spans: a validate child each, and 3 + 1
    durable commits (the run of three folds into one ring entry)."""
    TRACER.clear()
    for _ in range(2):
        with TRACER.span(tracing.STATE_APPLY_BLOCK):
            with TRACER.span(tracing.STATE_VALIDATE):
                spin(2)
            with TRACER.span(tracing.STATE_EXEC):
                for _ in range(3):
                    t0 = time.perf_counter_ns()
                    spin(1)
                    TRACER.leaf(tracing.DB_WRITE, t0, ops=1, bytes=8)
                    spin(0.2)
            with TRACER.span(tracing.STATE_SAVE):
                t0 = time.perf_counter_ns()
                spin(1)
                TRACER.leaf(tracing.DB_WRITE, t0, ops=4, bytes=64)
            spin(1)   # update_state: apply_block's own time


def test_stat_total_and_count_per_another_kind():
    two_blocks()
    r = readings()
    p50, note = program_span_stat.read(
        r, {"kind": "state.validate", "stat": "p50"})
    assert 2.0 <= p50 < 4.0 and note == {"spans": 2}
    per_block, note = program_span_stat.read(
        r, {"kind": "state.validate", "total_per": "state.apply_block"})
    assert 2.0 <= per_block < 4.0 and note["state.apply_block"] == 2
    # four durable commits a block in two ring entries; their own time,
    # not the time between the folded ones
    writes, note = program_span_stat.read(
        r, {"kind": "db.write", "count_per": "state.apply_block"})
    assert writes == 4.0 and note["entries"] == 4
    ms, _ = program_span_stat.read(
        r, {"kind": "db.write", "total_per": "state.apply_block"})
    assert 4.0 <= ms < 4.6


def test_per_a_driver_counter_and_nothing_to_read():
    two_blocks()
    r = readings({"blocks_applied": 4})
    got, note = program_span_stat.read(
        r, {"kind": "db.write", "count_per": "blocks_applied"})
    assert got == 2.0 and note["blocks_applied"] == 4
    # the parent commit's program has no such span: left out, no raise
    assert program_span_stat.read(
        r, {"kind": "admission.flush", "stat": "p95"}) is None
    assert program_span_stat.read(
        r, {"kind": "db.write", "count_per": "no_such_unit"}) is None
    assert program_span_self.read(r, {"kind": "verify.commit"}) is None
    TRACER.clear()
    assert program_span_stat.read(
        readings(), {"kind": "db.write", "stat": "p50"}) is None


def test_spans_outside_the_window_are_not_read():
    two_blocks()
    r = readings()
    with TRACER.span(tracing.STATE_APPLY_BLOCK):   # after the window
        with TRACER.span(tracing.STATE_VALIDATE):
            spin(20)
    _, note = program_span_stat.read(
        r, {"kind": "state.validate", "stat": "p95"})
    assert note == {"spans": 2}


def test_self_time_less_all_children_or_only_some():
    two_blocks()
    r = readings()
    own, note = program_span_self.read(r, {"kind": "state.apply_block"})
    assert 1.0 <= own < 2.0 and note["spans"] == 2
    assert note["whole_p50_ms"] > own + 6.0
    less_validate, _ = program_span_self.read(
        r, {"kind": "state.apply_block", "less": ["state.validate"]})
    assert abs(note["whole_p50_ms"] - less_validate - 2.0) < 1.0


def test_phase_times_from_the_recorded_chip_trace(tmp_path, monkeypatch):
    from benchmark import trace_reduce
    from tendermint_tpu.crypto.tpu import expanded

    small = os.path.join(DATA, "small.xplane.pb")
    if not os.path.exists(small):
        pytest.skip("no recorded trace in benchmark/tests/data")
    out = tmp_path / "trace" / "cell" / "plugins" / "profile" / "run"
    out.mkdir(parents=True)
    shutil.copy(small, out / "host.xplane.pb")
    monkeypatch.setattr(trace_scope, "OUT", str(tmp_path))
    monkeypatch.setattr(trace_scope, "_PHASES", None)
    reduced = trace_reduce.reduce_trace(small)
    top = reduced["device_ops"][0][0]       # convolution_tanh_fusion.2
    monkeypatch.setattr(expanded, "structured_phases",
                        lambda: {top: "ed25519.assemble"})
    r = types.SimpleNamespace(trace=reduced)
    ms, note = trace_scope.read(
        r, {"pattern": "small_program", "scope": "ed25519.assemble"})
    assert note["executions"] == 6
    assert abs(ms - 1e3 * reduced["device_ops"][0][1] / 6) < 1e-9
    assert 0.0 < note["covered_share"] < 1.0
    assert set(note["phases_ms"]) == {"ed25519.assemble", "unscoped"}
    # a phase no operation falls under, or a program that is not in
    # the slice, reads nothing
    assert trace_scope.read(
        r, {"pattern": "small_program", "scope": "ed25519.gather"}) is None
    assert trace_scope.read(
        r, {"pattern": "skernel", "scope": "ed25519.assemble"}) is None


@pytest.mark.parametrize("program", ["parent", "nothing_launched"])
def test_no_phase_map_no_metric(tmp_path, monkeypatch, program):
    """A program without the function (the parent commit), or one with
    no structured launch to map, reads nothing and does not raise."""
    from tendermint_tpu.crypto.tpu import expanded

    out = tmp_path / "trace" / "cell" / "plugins" / "profile" / "run"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(trace_scope, "OUT", str(tmp_path))
    monkeypatch.setattr(trace_scope, "_PHASES", None)
    if program == "parent":
        monkeypatch.delattr(expanded, "structured_phases")
    else:
        monkeypatch.setattr(expanded, "_CACHE", type(expanded._CACHE)())
    r = types.SimpleNamespace(trace={"modules": {
        "jit_skernel": {"count": 3, "total_s": 0.24}}})
    assert trace_scope.read(
        r, {"pattern": "skernel", "scope": "ed25519.assemble"}) is None
