"""One backend-classification vocabulary for the whole repo.

A process must be able to say which hardware ran its launches: a run
that lands on `TFRT_CPU_0` may never be read as a chip number. This
module is the single place that answers "is this device string a
chip?" — bench.py's backend stamp, tools/bench_trend.py's
misrepresentation check and the launch-ledger watchdog all import it
(string logic needs no jax, so tools/ scripts can import it too; the
two functions that ask the live backend import jax lazily).

Vocabulary:
  * ``platform()`` — ``jax.devices()[0].platform`` of this process,
    read once: the label lane counters carry and what
    ``require_tpu()`` (``[crypto] backend = "tpu"``) checks.
  * ``backend_label(device)`` — the stamp written into BENCH lines:
    ``"tpu"`` or ``"cpu-fallback"`` (hyphen; kept stable for recorded
    lines already on disk).
  * ``classify_stamps(...)`` — the trajectory-gate classifier:
    ``"silicon"`` / ``"cpu_fallback"`` (underscore; the bench_trend
    table vocabulary) plus the misrepresentation/unattribution
    problems.
  * ``effective_backend_states()`` — the watchdog's closed state set.
"""

from __future__ import annotations

import functools

# Substrings that mark a jax device string as host silicon-less
# execution (TFRT_CPU_0, "cpu:0", "host").
CPU_DEVICE_MARKERS = ("cpu", "host")
# Backend stamps that claim real accelerator silicon.
SILICON_BACKENDS = ("tpu", "silicon", "device")

# The watchdog's effective-backend classification (closed set; the
# tpu_effective_backend gauge is one-hot over exactly these):
#   tpu           — a successful launch landed on accelerator silicon
#                   within the window
#   mesh_degraded — launches are completing, but one or more mesh
#                   devices are breaker-evicted: the fabric serves on
#                   the SURVIVORS (verify continuity, not a backend
#                   fallback — the distinction the mesh degradation
#                   runbook triages on)
#   cpu_fallback  — launches are completing on CPU (or raising and
#                   degrading to host) with no silicon success in the
#                   window
#   idle          — records exist, but none within the window
#   unknown       — no device launch has ever been recorded
EFFECTIVE_STATES = ("tpu", "mesh_degraded", "cpu_fallback", "idle",
                    "unknown")


@functools.cache
def platform() -> str:
    """The platform of the process's default JAX device ("tpu",
    "cpu", ...), read once: a process never changes backend."""
    import jax

    return jax.devices()[0].platform


def require_tpu() -> None:
    """`[crypto] backend = "tpu"` is binding: refuse to start on any
    other default backend instead of serving from the host while the
    config says chip."""
    got = platform()
    if got != "tpu":
        raise RuntimeError(
            f'[crypto] backend = "tpu" but the default JAX backend is '
            f'{got!r}: refusing to start and verify on the host. Run '
            'on a machine whose chip this process can open, or set '
            'backend = "auto" (use the chip when present) or "cpu".')


def device_memory_bytes(device) -> int:
    """Bytes of memory the device itself reports
    (memory_stats()["bytes_limit"]); a device that reports none is an
    error, not a default."""
    limit = (device.memory_stats() or {}).get("bytes_limit")
    if not limit:
        raise RuntimeError(
            f"device {device} ({device.device_kind}) reports no "
            "memory_stats()['bytes_limit']: no table budget can be "
            "derived for it")
    return int(limit)


def device_is_cpu(device: str) -> bool:
    d = str(device).lower()
    return any(m in d for m in CPU_DEVICE_MARKERS)


def backend_label(device: str) -> str:
    """Device string -> the backend stamp bench.py / silicon records
    carry ("tpu" or "cpu-fallback")."""
    return "tpu" if "tpu" in str(device).lower() else "cpu-fallback"


def effective_state_of(device: str) -> str:
    """Device string of a completed launch -> the watchdog state it
    evidences ("tpu" or "cpu_fallback")."""
    return "tpu" if backend_label(device) == "tpu" else "cpu_fallback"


def classify_stamps(backend_stamp: str, cpu_fallback: bool,
                    device: str) -> tuple[str, list[str]]:
    """The trajectory-gate core (tools/bench_trend.py): a parsed BENCH
    payload's explicit stamps -> (``"silicon"`` | ``"cpu_fallback"``,
    problems). A silicon backend stamp contradicted by the fallback
    flag or a CPU device string is ``misrepresented``; a measured value
    with no stamps at all is ``unattributed`` — neither may extend the
    silicon trajectory."""
    problems: list[str] = []
    stamp = str(backend_stamp or "").lower()
    device = str(device or "")
    if stamp:
        claims_silicon = any(b in stamp for b in SILICON_BACKENDS) \
            and "cpu" not in stamp
        if claims_silicon and (cpu_fallback or device_is_cpu(device)):
            problems.append(
                f"misrepresented: backend stamp {stamp!r} but "
                f"cpu_fallback={cpu_fallback} device={device!r}")
            return "cpu_fallback", problems
        return ("silicon" if claims_silicon else "cpu_fallback"), problems
    if cpu_fallback or (device and device_is_cpu(device)):
        return "cpu_fallback", problems
    if device:
        return "silicon", problems
    # a measured value with no device/backend evidence at all cannot
    # claim the silicon trajectory
    problems.append(
        "unattributed: measured value with no device/backend stamp")
    return "cpu_fallback", problems
