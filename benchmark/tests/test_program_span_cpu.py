"""The reader of the thread-CPU time the program's spans carry
(`program_span_cpu`), on readings made up by hand: a thread that runs,
a thread that holds a span and waits, records without the attribute,
and no record at all."""

import time
import types

import pytest

from benchmark.layer_metrics import program_span_cpu
from tendermint_tpu.libs import tracing
from tendermint_tpu.libs.tracing import TRACER

pytestmark = pytest.mark.skipif(
    not hasattr(tracing, "STORE_ENCODE_COMMITS"),
    reason="the program stamps no cpu_ns on its spans")

PER = "state.apply_block"


def readings(counters=None):
    return types.SimpleNamespace(
        program_spans=[(r[0], r[4], r[5]) for r in TRACER.snapshot()],
        counters=counters or {})


def spin(ms: float) -> None:
    end = time.perf_counter() + ms / 1e3
    while time.perf_counter() < end:
        pass


def two_blocks():
    """Two blocks: a save that runs 4 ms in one child and waits 6 ms in
    the other, and a state.validate (which wraps an await: unmarked)."""
    TRACER.clear()
    for _ in range(2):
        with TRACER.span(tracing.STORE_SAVE_BLOCK):
            with TRACER.span(tracing.STORE_ENCODE_COMMITS):
                spin(4)
            with TRACER.span(tracing.STORE_ENCODE_PARTS):
                time.sleep(0.006)
        with TRACER.span(tracing.STATE_APPLY_BLOCK):
            with TRACER.span(tracing.STATE_VALIDATE):
                spin(1)


def test_cpu_is_what_the_thread_ran():
    two_blocks()
    ms, note = program_span_cpu.read(readings(), {
        "kinds": ["store.save_block"], "what": "cpu", "per": PER})
    # 4 ms of running a block; the sleep is not CPU
    assert 3.5 <= ms < 6.0
    assert note == {"entries": 2, PER: 2, "stamped_share": 1.0}
    ms, _ = program_span_cpu.read(readings(), {
        "kinds": ["store.encode_commits", "store.encode_parts"],
        "what": "cpu", "per": PER})
    assert 3.5 <= ms < 6.0


def test_wait_is_what_the_thread_held_and_did_not_run():
    two_blocks()
    ms, note = program_span_cpu.read(readings(), {
        "kinds": ["store.encode_commits", "store.encode_parts"],
        "what": "wait", "per": PER})
    assert 5.5 <= ms < 9.0 and note["entries"] == 4
    busy, _ = program_span_cpu.read(readings(), {
        "kinds": ["store.encode_commits"], "what": "wait", "per": PER})
    assert 0.0 <= busy < 1.0
    # per a counter the driver kept, where no span of that name exists
    ms4, note = program_span_cpu.read(readings({"blocks_applied": 4}), {
        "kinds": ["store.encode_parts"], "what": "wait",
        "per": "blocks_applied"})
    assert 2.7 <= ms4 < 4.5 and note["blocks_applied"] == 4


def test_records_without_the_attribute_are_left_out():
    two_blocks()
    # an unmarked kind alone reads nothing ...
    assert program_span_cpu.read(readings(), {
        "kinds": ["state.validate"], "what": "cpu", "per": PER}) is None
    # ... and beside a marked one it is counted in the share only
    ms, note = program_span_cpu.read(readings(), {
        "kinds": ["state.validate", "store.encode_commits"],
        "what": "cpu", "per": PER})
    assert 3.5 <= ms < 6.0
    assert note["entries"] == 2 and note["stamped_share"] == 0.5


def test_no_record_and_no_unit_read_nothing():
    two_blocks()
    assert program_span_cpu.read(readings(), {
        "kinds": ["verify.window"], "what": "cpu", "per": PER}) is None
    assert program_span_cpu.read(readings(), {
        "kinds": ["store.save_block"], "what": "cpu",
        "per": "no.such_unit"}) is None
    TRACER.clear()
    assert program_span_cpu.read(readings(), {
        "kinds": ["store.save_block"], "what": "cpu", "per": PER}) is None
