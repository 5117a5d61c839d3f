"""The churn cell's whole control flow at tiny sizes on the CPU: a sound
run is `correct`, each of its four controls is not, and with the timed
path broken underneath (a window that blames the wrong block, a node
that takes a validator update and keeps the old order) `correct` comes out false.
Slow (each kernel shape compiles for tens of seconds the first time)."""

import importlib
import types

import pytest

from benchmark.tests.test_rehearse import _run

CELL = "fastsync1k-churn.replay"


def test_rehearsal_is_correct_and_every_control_is_not():
    r = _run("--workload", CELL, "--seed", "2147483659", "--seconds", "20",
             "--trace", "0", "--rehearse", "--controls", "1")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
    lines = r.stdout.splitlines()
    assert '"correct": true' in lines[-1]
    assert "]: rehearsal finished" in lines[-1]
    controls = [ln for ln in lines if ": correct = " in ln]
    assert len(controls) == 4 and all(
        ln.endswith("correct = false") for ln in controls), controls
    # the planted commit was met and named, and sets were compared
    facts = next(ln for ln in lines if "correct: facts" in ln)
    assert '"refusals": [[[39, [' in facts
    assert '"sets_compared": 0' not in facts


def _main(monkeypatch, seconds="30"):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    run = importlib.import_module("benchmark.run")
    return run.main(["--workload", CELL, "--seed", "2147483660",
                     "--seconds", seconds, "--trace", "0", "--rehearse"])


def test_sees_a_window_that_blames_the_wrong_block(monkeypatch):
    from tendermint_tpu.blockchain import verify_ahead

    sound = verify_ahead._batch_verify_window

    def shifted(vals, chain_id, items):
        out = sound(vals, chain_id, items)
        for i, err in enumerate(out):
            if err is not None and i > 0:
                out[i - 1], out[i] = err, None
                break
        return out

    monkeypatch.setattr(verify_ahead, "_batch_verify_window", shifted)
    assert _main(monkeypatch) == 1


def test_sees_a_node_that_keeps_the_old_order(monkeypatch):
    """A set that takes its updates and leaves its order as it was:
    every hash and signature check of the program still agrees with
    itself, the model's order does not."""
    from tendermint_tpu.types import validator_set

    real = validator_set.ValidatorSet.update_with_change_set

    def unsorted(self, changes):
        before = [v.address for v in self.validators]
        real(self, changes)
        rank = {a: i for i, a in enumerate(before)}
        self.validators.sort(key=lambda v: rank.get(v.address, len(rank)))

    monkeypatch.setattr(validator_set.ValidatorSet,
                        "update_with_change_set", unsorted)
    # the chain is signed in the model's order: the first window under
    # the new set is refused where nothing was forged
    assert _main(monkeypatch) == 1


def test_plan_walks_a_replays_windows_and_names_the_bucket():
    from benchmark.traffic import fastsync_churn_replay as churn
    from tendermint_tpu.crypto.tpu.expanded import ExpandedKeys

    for lanes in (1, 84, 128, 129, 655, 1000, 1024, 1025, 10_241, 10_480,
                  11_264, 11_265):
        assert churn.lane_bucket(lanes) == ExpandedKeys._bucket(lanes)
    for blocks, every, updates, commits in (
            (130, 10, list(range(10, 121, 10)), {16, 12, 8, 2}),
            (40, 10, [10, 20, 30], {16, 12, 8, 2}),
            (64, 5, list(range(5, 61, 5)), {16, 12, 11, 7, 6, 2, 1})):
        d = object.__new__(churn.Driver)
        d.run = types.SimpleNamespace(params={
            "update_every_blocks": every, "blocks": blocks})
        d._plan(16)
        assert (d.length, d.bad, d.end) == (blocks, blocks - 1, blocks - 2)
        assert (d.updates, d.window_commits) == (updates, commits)
    d.run.params.update(blocks=22, update_every_blocks=10)
    with pytest.raises(Exception, match="no update of each kind"):
        d._plan(16)


def test_span_total_reads_zero_for_a_kind_that_never_began():
    import time

    from benchmark.layer_metrics import program_span_stat, program_span_total
    from tendermint_tpu.libs import tracing
    from tendermint_tpu.libs.tracing import TRACER

    TRACER.clear()
    for wait_ms in (0, 3):
        with TRACER.span(tracing.STATE_APPLY_BLOCK):
            if wait_ms:
                with TRACER.span(tracing.CRYPTO_TABLE_WAIT, keys=3):
                    time.sleep(wait_ms / 1e3)
    r = types.SimpleNamespace(
        program_spans=[(x[0], x[4], x[5]) for x in TRACER.snapshot()],
        counters={})
    wait = {"kind": "crypto.table_wait", "total_per": "state.apply_block"}
    value, note = program_span_total.read(r, wait)
    assert value == program_span_stat.read(r, wait)[0] >= 1.5
    assert note == {"entries": 1, "occurrences": 1, "state.apply_block": 2}
    # registered and never begun: 0.0, where program_span_stat reads nothing
    build = dict(wait, kind="crypto.table_build")
    assert program_span_total.read(r, build)[0] == 0.0
    assert program_span_stat.read(r, build) is None
    # a program without the kind: nothing
    assert program_span_total.read(r, dict(wait, kind="no.such")) is None
