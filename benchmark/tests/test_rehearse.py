"""Each driver's whole control flow at tiny sizes on the CPU: a sound
run is `correct`, every control is not, a run off the chip prints no
result line, and with the timed path broken underneath `correct` comes
out false. Slow (each kernel shape compiles for tens of seconds the
first time)."""

import os
import subprocess
import sys

import pytest

from benchmark.harness import REPO

CELLS = ("commit10k.replay", "kvstore1.signed-closed", "fastsync1k.replay")


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"), *args],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=1500)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_is_correct_and_every_control_is_not(cell):
    r = _run("--workload", cell, "--seed", "2147483659", "--seconds", "20",
             "--trace", "0", "--rehearse", "--controls", "1")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
    lines = r.stdout.splitlines()
    assert '"correct": true' in lines[-1]
    assert "]: rehearsal finished" in lines[-1]
    controls = [ln for ln in lines if ": correct = " in ln]
    assert controls and all(ln.endswith("correct = false")
                            for ln in controls), controls


def test_no_result_line_without_the_chip():
    r = _run("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert r.returncode != 0
    assert not any(ln.startswith("{") for ln in r.stdout.splitlines())


def _main_with(monkeypatch, cell, seconds="3"):
    import importlib

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    run = importlib.import_module("benchmark.run")
    return run.main(["--workload", cell, "--seed", "2147483660",
                     "--seconds", seconds, "--trace", "0", "--rehearse"])


def test_commit_cell_sees_a_kernel_that_accepts_everything(monkeypatch):
    import numpy as np

    from tendermint_tpu.crypto.tpu import expanded

    def all_true(self, indices, sbatch, sigs):
        return np.ones(len(indices), bool)

    monkeypatch.setattr(expanded.ExpandedKeys, "verify_structured", all_true)
    assert _main_with(monkeypatch, CELLS[0]) == 1


def test_node_cell_sees_an_admission_plane_that_verifies_nothing(monkeypatch):
    import numpy as np

    from tendermint_tpu.mempool import admission

    monkeypatch.setattr(admission.AdmissionCollector, "_verify_batch",
                        lambda self, envs: np.ones(len(envs), bool))
    assert _main_with(monkeypatch, CELLS[1]) == 1


def test_sync_cell_sees_a_window_that_blames_the_wrong_block(monkeypatch):
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
    # long enough for a CPU to replay the tiny chain to its end
    assert _main_with(monkeypatch, CELLS[2], seconds="30") == 1
