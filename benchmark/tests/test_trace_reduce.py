"""trace_reduce.py: its arithmetic on made-up events, and the whole
reduction on a small trace recorded on the chip (data/)."""

import glob
import os

import pytest

from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_merges_overlaps():
    assert tr._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_self_time_does_not_count_a_loop_body_twice():
    events = [(0, 100, "while"), (10, 30, "fusion.1"), (50, 40, "fusion.2"),
              (200, 10, "copy")]
    got = tr._self_times(events)
    assert got == {"while": 30e-9, "fusion.1": 30e-9, "fusion.2": 40e-9,
                   "copy": 10e-9}


def test_gap_goes_to_the_shortest_span_that_covers_it():
    spans = [(0, 100, "verify_commit"), (20, 40, "crypto.pack")]
    got = tr._label_gap((10, 60), spans)
    assert got == {"verify_commit": 30, "crypto.pack": 20}
    assert tr._label_gap((150, 160), spans) == {"host:unattributed": 10}


def test_recorded_chip_trace():
    found = glob.glob(os.path.join(DATA, "*.xplane.pb"))
    if not found:
        pytest.skip("no recorded trace in benchmark/tests/data")
    out = tr.reduce_trace(found[0])
    assert out["devices"] >= 1 and out["busy_s"] > 0
    assert out["modules"], "no XLA Modules line: kernel_ms has no source"
    assert all(m["count"] > 0 and m["total_s"] > 0
               for m in out["modules"].values())
    assert out["device_ops"] and len(out["device_ops"]) <= 10
    total_ops = sum(s for _, s in out["device_ops"])
    assert total_ops <= out["busy_s"] * 1.001   # self times, no overlap
    if os.path.basename(found[0]) == "small.xplane.pb":
        # record_trace.py: six launches of one small program, each
        # followed by a 20 ms pause of the host
        assert out["modules"]["jit_small_program"]["count"] == 6
        assert abs(out["busy_s"] - 2.4378e-05) < 1e-9
        assert out["device_ops"][0][0] == "convolution_tanh_fusion.2"
        assert out["idle_gaps"][0][0] == "pause"
        assert 0.09 < out["idle_gaps"][0][1] < 0.13
