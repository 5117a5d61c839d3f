"""The mixed-key evidence cell's whole control flow at tiny sizes on the
CPU: a sound run is `correct`, each of its five controls is not, and
with the timed path broken underneath (an sr25519 kernel that accepts
every lane, an evidence check that verifies nothing) the run fails.
Slow: eight kernel shapes compile for tens of seconds each the first
time, and the window has to hold a whole even and a whole odd replay."""

import importlib

import numpy as np

from benchmark.tests.test_rehearse import _run

CELL = "mixed1k-evidence.replay"
SECONDS = "90"


def test_rehearsal_is_correct_and_every_control_is_not():
    r = _run("--workload", CELL, "--seed", "2147483659", "--seconds",
             SECONDS, "--trace", "0", "--rehearse", "--controls", "1")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
    lines = r.stdout.splitlines()
    assert '"correct": true' in lines[-1]
    assert "]: rehearsal finished" in lines[-1]
    controls = [ln for ln in lines if ": correct = " in ln]
    assert len(controls) == 5 and all(
        ln.endswith("correct = false") for ln in controls), controls
    # both planted faults were met, each named
    facts = next(ln for ln in lines if "correct: facts" in ln)
    assert '[["commit", 39, [' in facts
    assert '[["evidence", 38, 60, "invalid signature on vote B"]]' in facts
    # and the warm replay's pool refused the probe list for its item
    assert '"probe": [60, "invalid signature on vote B"]' in facts
    assert '"host_verify_span_lanes": 0' in facts
    assert "correct: host_verified_lanes = 0 " in r.stdout


def _main(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    run = importlib.import_module("benchmark.run")
    return run.main(["--workload", CELL, "--seed", "2147483660",
                     "--seconds", SECONDS, "--trace", "0", "--rehearse"])


def test_sees_an_sr25519_kernel_that_accepts_every_lane(monkeypatch):
    """The planted commit's sr25519 lane goes through: the warm replay
    already applies a block it must refuse."""
    from tendermint_tpu.crypto.tpu import sr_verify

    monkeypatch.setattr(sr_verify, "verify_batch_sr",
                        lambda pubs, msgs, sigs, ctx=b"", *, cpu=False:
                        np.ones(len(pubs), bool))
    assert _main(monkeypatch) == 1


def test_sees_an_evidence_check_that_verifies_nothing(monkeypatch):
    """Every list passes, the odd replays' fifth with it: no replay is
    refused at the planted evidence."""
    import tendermint_tpu.evidence as pool_mod

    monkeypatch.setattr(pool_mod, "signature_errors",
                        lambda chain_id, vals, items: [None] * len(items))
    assert _main(monkeypatch) == 1


def test_shapes_name_the_programs_buckets():
    from benchmark.traffic.fastsync_churn_replay import lane_bucket
    from tendermint_tpu.crypto.tpu.expanded import ExpandedKeys

    # the sr25519 launch buckets as the ed25519 ones do
    # (crypto/tpu/sr_verify.py restates ExpandedKeys._bucket)
    for lanes in (48, 72, 80, 222, 333, 660, 768, 1024, 1025, 3552, 7120):
        assert lane_bucket(lanes) == ExpandedKeys._bucket(lanes)
