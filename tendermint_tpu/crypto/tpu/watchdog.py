"""Silicon watchdog: the *effective* verify backend, from evidence.

The configured backend (`[crypto] backend` in the node config) says
what the operator believes; the launch ledger says what actually
happened. This module closes the loop: it classifies the effective
backend from recent ledger records and turns launches that land on
the host while a chip was promised — a run on TFRT_CPU_0 that nothing
in the process could name — into a named, alerting `/status`
condition within ONE launch.

Classification (crypto/tpu/backend.py EFFECTIVE_STATES):

    tpu           a successful launch landed on accelerator silicon
                  inside the window
    cpu_fallback  launches are completing on CPU, or raising and
                  degrading to host, with no silicon success inside
                  the window
    idle          records exist, but none inside the window
    unknown       no device launch has ever been recorded

With `crypto.backend = "tpu"` configured, the device check degrades
when any of these hold:

  * effective backend is cpu_fallback (launches landing on CPU or
    raising);
  * records exist but no successful launch completed within the
    window (`crypto.watchdog_window_s`);
  * an operator gave a device exec baseline
    (TM_TPU_SILICON_BASELINE_MS) and the p50 over the window's
    silicon launches drifts more than DRIFT_FACTOR x past it — the
    repo records no baseline of its own, so without the variable
    there is no drift check;
  * any chip's registered HBM-resident bytes exceed its capacity
    budget.

A healthy breaker probe (one successful silicon launch) flips the
verdict back to ok — recovery is also within one launch. With
backend "auto" (default) or "cpu" the watchdog reports but never
degrades: running on CPU is only a lie when silicon was promised.

Pure module (no jax): the /status path must never initiate backend
bring-up.
"""

from __future__ import annotations

import os
import threading

from . import backend as _backend
from . import ledger as _ledger

DRIFT_FACTOR = 3.0
DEFAULT_WINDOW_S = 60.0
# Per-chip HBM budget the accounting registry is checked against when
# the platform doesn't say better (v5e: 16 GB/chip).
DEFAULT_HBM_BUDGET_BYTES = 16 * 1024**3

_LOCK = threading.Lock()
_CONFIGURED = "auto"
_WINDOW_S = DEFAULT_WINDOW_S

# Zero-arg callable returning the currently breaker-evicted mesh
# device strings (pure read — no probes, no jax). crypto/batch.py
# registers it at import; the watchdog stays importable (and /status
# servable) in processes that never load the breaker stack.
_EVICTED_SUPPLIER = None


def register_evicted_supplier(fn) -> None:
    global _EVICTED_SUPPLIER
    _EVICTED_SUPPLIER = fn


def evicted_mesh_devices() -> list[str]:
    """Mesh devices currently evicted by per-device breakers ([] when
    no supplier is registered or the read fails)."""
    if _EVICTED_SUPPLIER is None:
        return []
    try:
        return sorted(_EVICTED_SUPPLIER())
    except Exception:  # pragma: no cover - status read never fatal
        return []


def configure(backend: str = "auto",
              window_s: float = DEFAULT_WINDOW_S) -> None:
    """node._build pushes the [crypto] config section here (module-
    level setter, the resident.set_arena_shards pattern)."""
    global _CONFIGURED, _WINDOW_S
    with _LOCK:
        _CONFIGURED = str(backend or "auto")
        _WINDOW_S = float(window_s) if window_s and window_s > 0 \
            else DEFAULT_WINDOW_S


def configured_backend() -> str:
    return _CONFIGURED


def window_s() -> float:
    return _WINDOW_S


def silicon_baseline_ms() -> float | None:
    """Device exec ms/launch the drift check compares against: the
    operator's TM_TPU_SILICON_BASELINE_MS, or None (no drift check)."""
    env = os.environ.get("TM_TPU_SILICON_BASELINE_MS")
    try:
        return float(env) if env else None
    except ValueError:
        return None


_SUCCESS_VERDICTS = ("ok", "invalid")  # the launch itself completed


def classify(records: list[dict] | None = None) -> dict:
    """Effective-backend classification over the ledger (or an
    explicit record list, newest last). Updates the one-hot
    tpu_effective_backend gauge."""
    import time as _t

    win = _WINDOW_S
    if records is None:
        all_recs = _ledger.snapshot()
    else:
        all_recs = list(records)
    now = _t.monotonic()
    recent = [r for r in all_recs if now - r["mono"] <= win]
    succ = [r for r in recent if r["verdict"] in _SUCCESS_VERDICTS]
    silicon = [r for r in succ
               if _backend.effective_state_of(r["device"]) == "tpu"]

    if not all_recs:
        state = "unknown"
    elif not recent:
        state = "idle"
    elif silicon:
        state = "tpu"
    else:
        state = "cpu_fallback"
    evicted = evicted_mesh_devices()
    if evicted and succ and state in ("tpu", "cpu_fallback"):
        # launches are completing while per-device breakers hold chips
        # out of the mesh: degraded-mode verify CONTINUITY on the
        # survivors, not a backend flip — named so the runbook (and
        # the one-hot gauge) can tell the two apart
        state = "mesh_degraded"

    last_ok = max((r["mono"] for r in succ), default=None)
    last_any = max((r["mono"] for r in all_recs), default=None)
    exec_ms = [r["stages_ms"]["exec"] for r in (silicon or succ)
               if r.get("stages_ms", {}).get("exec") is not None]
    out = {
        "effective_backend": state,
        "configured_backend": _CONFIGURED,
        "evicted_devices": evicted,
        "window_s": win,
        "launches_in_window": len(recent),
        "last_device_launch_age_s": (
            round(now - last_ok, 3) if last_ok is not None else None),
        "last_record_age_s": (
            round(now - last_any, 3) if last_any is not None else None),
        "exec_p50_ms": _ledger._pctl(exec_ms, 0.5) if exec_ms else None,
    }
    _set_gauge(state)
    return out


def _set_gauge(state: str) -> None:
    try:
        from ...libs.metrics import tpu_metrics

        g = tpu_metrics().effective_backend
        for s in _backend.EFFECTIVE_STATES:
            g.set(1 if s == state else 0, backend=s)
    except Exception:  # pragma: no cover - metrics never fatal
        pass


def hbm_check(budget_bytes: int = DEFAULT_HBM_BUDGET_BYTES) -> dict:
    """Registered device-resident bytes per chip vs the per-chip
    budget; over-budget chips are named."""
    totals = _ledger.hbm_device_totals()
    over = {d: n for d, n in totals.items() if n > budget_bytes}
    return {"totals": totals, "budget_bytes": budget_bytes,
            "over_budget": over}


def verdict() -> dict:
    """The /status device-check contribution: classification + an
    ok/degraded status with a reason string. Degrades only when
    silicon was promised (configured backend "tpu") but the ledger
    shows otherwise."""
    cls = classify()
    out = dict(cls)
    out["status"] = "ok"
    hbm = hbm_check()
    if hbm["over_budget"]:
        out["status"] = "degraded"
        out["reason"] = (
            "HBM over budget on {}".format(", ".join(
                f"{d} ({n} B)"
                for d, n in sorted(hbm["over_budget"].items()))))
        out["hbm_over_budget"] = hbm["over_budget"]
        return out
    if _CONFIGURED != "tpu":
        return out
    state = cls["effective_backend"]
    if state == "mesh_degraded":
        ev = cls["evicted_devices"]
        out["status"] = "degraded"
        out["reason"] = (
            "{} mesh device(s) evicted by per-device breakers ({}); "
            "verify continues on the surviving devices until a "
            "half-open probe re-admits them".format(
                len(ev), ", ".join(ev)))
    elif state == "cpu_fallback":
        out["status"] = "degraded"
        out["reason"] = (
            "crypto.backend=tpu but launches are landing on CPU or "
            "raising (effective_backend=cpu_fallback; last successful "
            "device launch {}s ago)".format(
                cls["last_device_launch_age_s"]))
    elif state == "idle":
        out["status"] = "degraded"
        out["reason"] = (
            "crypto.backend=tpu but no device launch completed within "
            f"the {cls['window_s']}s watchdog window")
    elif state == "tpu":
        base = silicon_baseline_ms()
        p50 = cls["exec_p50_ms"]
        if base and p50 and p50 > DRIFT_FACTOR * base:
            out["status"] = "degraded"
            out["baseline_ms"] = base
            out["reason"] = (
                f"device exec p50 {p50} ms drifted >"
                f"{DRIFT_FACTOR:g}x past the operator's silicon "
                f"baseline {base} ms")
    # state "unknown" (nothing ever launched) stays ok: a freshly
    # booted node that hasn't verified yet is not degraded.
    return out
