"""The live-consensus cell's whole control flow at 216 validators on the
CPU: a sound run is `correct`, each control is not, and with the vote
scheduler's verifier broken underneath (it accepts every lane) the
planted copies are tallied and `correct` comes out false. Slow: the
node's start loads two programs (the structured one at the rehearsal's
128 lanes, the 256-lane arena's), tens of seconds each the first time."""

import importlib

import numpy as np

from benchmark.tests.test_rehearse import _run

CELL = "consensus10k.live"


def test_rehearsal_is_correct_and_every_control_is_not():
    r = _run("--workload", CELL, "--seed", "2147483659", "--seconds",
             "20", "--trace", "0", "--rehearse", "--controls", "1")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
    lines = r.stdout.splitlines()
    assert '"correct": true' in lines[-1]
    assert "]: rehearsal finished" in lines[-1]
    controls = [ln for ln in lines if ": correct = " in ln]
    assert len(controls) == 2 and all(
        ln.endswith("correct = false") for ln in controls), controls


def test_sees_a_scheduler_that_accepts_every_lane(monkeypatch):
    """The spoiled copies arrive first and are tallied; the good ones
    are then duplicates of another signature. The seen commits hold
    signatures that were never sent as good."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    from tendermint_tpu.types.validator_set import ValidatorSet

    monkeypatch.setattr(
        ValidatorSet, "verify_live",
        lambda self, lanes, build, materialize, sigs, launch_lanes:
        np.ones(len(lanes), bool))
    run = importlib.import_module("benchmark.run")
    assert run.main(["--workload", CELL, "--seed", "2147483660",
                     "--seconds", "20", "--trace", "0", "--rehearse"]) == 1
