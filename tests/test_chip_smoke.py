"""chip_smoke.py on the CPU: the rehearsal runs every phase at tiny
sizes, a phase made to fail ends it non-zero, and nothing run here may
print the result line the driver reads from the chip.

The script is run as the driver runs it — a process of its own — with
the tests' 8-device XLA_FLAGS taken out of its environment (one device,
as on the one-chip machine). Each kernel shape costs ~20 s on the CPU
even from a warm compile cache (the trace alone is ~10 s), so the
rehearsal is minutes, not seconds; it shares the suite's cache."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _smoke(*args, **env_extra):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", **env_extra)
    p = subprocess.run([sys.executable, SMOKE, *args], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=1200)
    lines = [json.loads(ln) for ln in p.stdout.splitlines()
             if ln.startswith("{")]
    return p, lines


def _no_result_line(p, lines):
    assert not any(ln.get("ok") for ln in lines), p.stdout
    assert '"platform": "tpu"' not in p.stdout


def test_rehearsal_runs_every_phase_and_prints_no_result_line():
    p, lines = _smoke("--rehearse")
    assert p.returncode == 0, p.stderr[-4000:]
    by_phase = {ln["phase"]: ln for ln in lines}
    assert list(by_phase) == ["setup", "A", "B", "C", "done"]
    a, b, c = by_phase["A"], by_phase["B"], by_phase["C"]
    assert a["committed"] == 98 and a["rejected_bad_signature"] == 3
    assert a["admission_launches"] >= 1 and a["host_fallbacks"] == 0
    assert a["breakers"] == {"ed25519": "closed", "sr25519": "closed"}
    assert a["stopped"] == "clean"
    assert b["validators"] == 256 and b["rejected_indexes"] == [85, 171]
    assert b["lanes_equal_ed25519_ref"] and c["lanes_equal_sr25519_ref"]
    assert c["zip215_only_accepted"] and c["arena_lanes_equal_reference"]
    assert c["launches"]["resident"] == 2
    _no_result_line(p, lines)


def test_failed_phase_exits_nonzero():
    """An armed device.verify failpoint makes the node's first device
    batch raise and degrade to the host — which the smoke must refuse
    to pass over."""
    p, lines = _smoke("--rehearse", TM_TPU_FAILPOINTS="device.verify=error")
    assert p.returncode != 0
    assert "node left the chip" in p.stderr, p.stderr[-4000:]
    assert [ln["phase"] for ln in lines] == ["setup"]
    _no_result_line(p, lines)


def test_default_run_fails_at_once_without_a_tpu():
    p, lines = _smoke()
    assert p.returncode != 0 and "no TPU" in p.stderr
    _no_result_line(p, lines)
