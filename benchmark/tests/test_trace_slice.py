"""The profiler slice's length: fixed, or ended by launches (no profiler
is started here; `_hold` is the part that decides)."""

import threading
import time

from benchmark import harness
from tendermint_tpu.crypto.tpu import ledger


def _held(slice_) -> float:
    t0 = time.perf_counter()
    slice_._hold(t0)
    return time.perf_counter() - t0


def test_a_fixed_slice_lasts_its_length():
    s = harness.TraceSlice("t-fixed", 10.0, 0.2)
    assert s.delay_s == (10.0 - 0.2) / 2
    assert 0.2 <= _held(s) < 0.4


def test_a_slice_bounded_by_launches_ends_on_the_last_of_them():
    s = harness.TraceSlice("t-launches", 10.0, 3.0, launches=2)

    def launches():
        # one while the profiler arms (not counted), two after it
        for at in (0.1, 0.25, 0.3):
            time.sleep(at)
            ledger.record(kernel="general", workload="test")

    t = threading.Thread(target=launches)
    t.start()
    held = _held(s)
    t.join()
    assert s.ARM_S + 0.3 <= held < 1.0


def test_a_slice_bounded_by_launches_ends_at_its_length_without_them():
    s = harness.TraceSlice("t-none", 10.0, 0.5, launches=2)
    assert 0.5 <= _held(s) < 0.7
