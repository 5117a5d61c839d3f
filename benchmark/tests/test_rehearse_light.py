"""The light-proxy cell's whole control flow at 24 validators, 96
heights and 16 callers on the CPU: a sound run is `correct`, each of
the three controls is not, and with the plane's verifier broken
underneath (it accepts every lane, or launches fewer lanes than it
journals) `correct` comes out false. The proxy's start loads the general program
at the rehearsal's 128 lanes, about a minute the first time."""

import importlib

import numpy as np

from benchmark.tests.test_rehearse import _run

CELL = "hub150.light-serve"


def test_rehearsal_is_correct_and_every_control_is_not():
    r = _run("--workload", CELL, "--seed", "2147483671", "--seconds",
             "8", "--trace", "0", "--rehearse", "--controls", "1")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
    lines = r.stdout.splitlines()
    assert '"correct": true' in lines[-1]
    assert "]: rehearsal finished" in lines[-1]
    controls = [ln for ln in lines if ": correct = " in ln]
    assert len(controls) == 3 and all(
        ln.endswith("correct = false") for ln in controls), controls
    window = next(ln for ln in lines if "]: window " in ln)
    assert '"compiled_in_window": 0' in window and '"failed": 0' in window


def test_sees_a_plane_that_accepts_every_lane(monkeypatch):
    """The flipped bit and S + L verify; the forged headers are stored
    and served, and the model, replayed beside the plane's journal,
    decides each of those steps otherwise."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    from tendermint_tpu.light.serving import LightVerifyCollector

    monkeypatch.setattr(
        LightVerifyCollector, "_verify_triples",
        lambda self, triples: np.ones(len(triples), bool))
    run = importlib.import_module("benchmark.run")
    assert run.main(["--workload", CELL, "--seed", "2147483672",
                     "--seconds", "8", "--trace", "0", "--rehearse"]) == 1


def test_sees_a_plane_that_launches_fewer_lanes_than_it_journals(
        monkeypatch):
    """Every plan is journalled at its width, and a launch holds one
    lane less than its cut (the first, never a spoiled one, is taken as
    good): every step's lanes and verdict still equal the model's, and
    only the launch ledger's lanes beside the model's tell."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    from tendermint_tpu.light.serving import LightVerifyCollector

    real = LightVerifyCollector._verify_triples

    def short(self, triples):
        return np.insert(real(self, triples[1:]), 0, True)

    monkeypatch.setattr(LightVerifyCollector, "_verify_triples", short)
    run = importlib.import_module("benchmark.run")
    assert run.main(["--workload", CELL, "--seed", "2147483674",
                     "--seconds", "8", "--trace", "0", "--rehearse"]) == 1


def test_sees_a_client_that_walks_the_hash_chain(monkeypatch):
    """The parent's rule (every height under the trusted head by a hash
    walk, no signature) in the program's place: the walks after the
    first latest are counted and the model decides the cases
    otherwise."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    from tendermint_tpu.light.client import Client

    real = Client.trusted_base

    def walks(self, height, live=None):
        if height < self.store.latest_height():
            return None
        return real(self, height, live)

    monkeypatch.setattr(Client, "trusted_base", walks)
    run = importlib.import_module("benchmark.run")
    assert run.main(["--workload", CELL, "--seed", "2147483673",
                     "--seconds", "8", "--trace", "0", "--rehearse"]) == 1
