"""Device launch ledger: one structured record per kernel launch.

Every device dispatch site in the verify stack — the general kernel
chunks (verify.py), the expanded/structured and mesh-sharded launches
(expanded.py), the resident arenas (resident.py), sr25519
(sr_verify.py), and through them the consensus, speculation,
admission, light-serving, fast-sync, probe, and bench planes — emits
one record into a bounded process-global ring. The ledger answers:
which hardware actually executed this launch, what did each
millisecond and byte buy, and is the device we think we're on
actually serving?  (A run on TFRT_CPU_0 must be nameable by the
process itself, not noticed by a human afterwards.)

A record is a plain dict:

    wall / mono        timestamps (time.time / time.monotonic)
    dur_ms             begin -> finalize wall time of the launch
    workload           consensus|speculation|admission|light|fastsync|
                       probe|bench (contextvar; callers tag planes)
    kernel             general|expanded|structured|*_sharded|
                       resident|resident_mesh|sr25519|sr25519_cpu
    backend / device   classified via crypto/tpu/backend.py from the
                       device string the verdict array landed on
    n_devices          devices the launch spanned (mesh shards)
    lanes / capacity / occupancy
                       real lanes vs the padded bucket executed
    bytes_h2d          host->device payload (for arena launches the
                       DELTA actually shipped, not the resident bytes)
    bytes_d2h          verdict readback bytes
    compile_cache      hit|miss (verify.count_compile's shape set)
    stages_ms          pack/dispatch/exec/readback — timed
                       around the SAME blocks the PR-1 span kinds
                       already bracket (zero new hot-path span sites)
    shard_lanes        per-device lane distribution on the mesh
    verdict            ok|invalid|sentinel_failed|raised
    ok_lanes / error

Consumers: the silicon watchdog (watchdog.py) classifies the
*effective* backend from recent records; /debug/launches exports the
ring; rollup() feeds bench.py BENCH lines and the e2e run report;
tools/launch_ledger.py prints cost-attribution tables. The disarmed
cost of a record (no consumers attached) is one small dict build plus
a deque append per LAUNCH — launches are milliseconds, the record is
microseconds (tools/check_ledger.py measures it against the
tools/check_spans.py per-span budget).

The module is deliberately jax-free: recording must work (and tests
must run) wherever numpy does.
"""

from __future__ import annotations

import contextvars
import sys
import threading
import time
from collections import deque

from . import backend as _backend

# Workload tags (closed set; the lint and docs table enumerate it).
WORKLOADS = ("consensus", "votes", "speculation", "admission", "light",
             "fastsync", "evidence", "probe", "bench")

DEFAULT_CAPACITY = 512

_LOCK = threading.Lock()
_RING: deque = deque(maxlen=DEFAULT_CAPACITY)
_EVICTED = 0

_WORKLOAD: contextvars.ContextVar[str] = contextvars.ContextVar(
    "tm_tpu_launch_workload", default="consensus")

# The record of the `with ledger.launch(...)` block this thread is in:
# how a launch function called inside the block (ExpandedKeys._launch*)
# reaches the record its caller opened.
_OPEN: contextvars.ContextVar["LaunchRecord | None"] = \
    contextvars.ContextVar("tm_tpu_launch_record", default=None)


# ---------------------------------------------------------------- workload


class _WorkloadCtx:
    __slots__ = ("_tag", "_token")

    def __init__(self, tag: str):
        self._tag = tag

    def __enter__(self):
        self._token = _WORKLOAD.set(self._tag)
        return self._tag

    def __exit__(self, *exc) -> bool:
        _WORKLOAD.reset(self._token)
        return False


def workload(tag: str) -> _WorkloadCtx:
    """Tag every launch recorded inside the block with `tag` — the
    plane entry points (admission flush, light flush, speculation
    launch, fast-sync window, breaker probes, bench workers) wrap
    their verify calls in this. Contextvar-scoped, so concurrent
    planes in one process can't mislabel each other's launches."""
    return _WorkloadCtx(tag)


def current_workload() -> str:
    return _WORKLOAD.get()


# ---------------------------------------------------------------- records


class _StageCtx:
    __slots__ = ("_rec", "_name", "_t0")

    def __init__(self, rec, name):
        self._rec = rec
        self._name = name

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        dt = (time.perf_counter() - self._t0) * 1e3
        st = self._rec.stages_ms
        st[self._name] = round(st.get(self._name, 0.0) + dt, 4)
        return False


def device_of(arr) -> tuple[str, int]:
    """(device string, device count) a jax array actually lives on;
    falls back to the process default device (or "") for plain numpy
    results from fake/test kernels. Never imports jax itself."""
    try:
        devs = arr.devices()  # jax.Array: set of Device
        devs = sorted(str(d) for d in devs)
        if devs:
            return devs[0], len(devs)
    except Exception:
        pass
    try:
        d = getattr(arr, "device", None)
        if d is not None and not callable(d):
            return str(d), 1
    except Exception:
        pass
    return default_device_str(), 1


def default_device_str() -> str:
    """str(jax.devices()[0]) when jax is already loaded in this
    process (a launch just ran, so the backend is initialized), else
    "". sys.modules probe only — the ledger never initiates the
    backend bring-up (which opens the chip) itself."""
    jax = sys.modules.get("jax")
    if jax is None:
        return ""
    try:
        return str(jax.devices()[0])
    except Exception:
        return ""


def nbytes_of(obj) -> int:
    """Total .nbytes over a (possibly nested) dict/tuple/list of
    arrays — the H2D payload estimate dispatch sites feed records."""
    if obj is None:
        return 0
    if isinstance(obj, dict):
        return sum(nbytes_of(v) for v in obj.values())
    if isinstance(obj, (tuple, list)):
        return sum(nbytes_of(v) for v in obj)
    try:
        return int(obj.nbytes)
    except (AttributeError, TypeError):
        return 0


class LaunchRecord:
    """One in-flight launch. Dispatch sites fill the fields they know
    and call done()/fail(); `with ledger.launch(...) as rec:` does the
    exception bookkeeping for straight-line sites."""

    __slots__ = ("kernel", "workload", "wall", "mono", "_t0",
                 "lanes", "capacity", "bytes_h2d", "bytes_d2h",
                 "compile_hit", "rows", "device", "n_devices",
                 "shard_lanes",
                 "active_devices", "verdict", "ok_lanes", "stages_ms",
                 "error", "_done", "_restamp")

    def __init__(self, kernel: str):
        self.kernel = kernel
        self.workload = _WORKLOAD.get()
        self.wall = time.time()
        self.mono = time.monotonic()
        self._t0 = time.perf_counter()
        self.lanes = 0
        self.capacity = 0
        self.bytes_h2d = 0
        self.bytes_d2h = 0
        self.compile_hit: bool | None = None
        # the comb-table read of a launch on expanded tables
        # (crypto/tpu/expanded.py): gathered | in_order; None elsewhere
        self.rows: str | None = None
        self.device = ""
        self.n_devices = 1
        self.shard_lanes: list[int] | None = None
        # Device set the launch actually spanned (mesh launches stamp
        # the EFFECTIVE mesh) — lets consumers (bench_trend, the mesh
        # degradation runbook) tell a degraded round from a full one.
        self.active_devices: list[str] | None = None
        self.verdict = ""
        self.ok_lanes = 0
        self.stages_ms: dict[str, float] = {}
        self.error: str | None = None
        self._done = False
        self._restamp = True

    def stage(self, name: str) -> _StageCtx:
        """Time a pipeline stage (pack/dispatch/exec/readback) —
        wrapped around the SAME blocks the existing
        crypto.* spans bracket, so stage attribution and the span
        kinds can never disagree."""
        return _StageCtx(self, name)

    def verdicts(self, arr) -> None:
        """Summarize a (lanes,) bool verdict array. Leaves an
        explicitly-set verdict (sentinel_failed) alone."""
        try:
            import numpy as np

            a = np.asarray(arr, bool)
            self.ok_lanes = int(a.sum())
            if not self.verdict:
                self.verdict = "ok" if bool(a.all()) else "invalid"
        except Exception:
            pass

    def result(self, arr) -> None:
        """Device/readback bookkeeping off the verdict array: device
        string + count and D2H bytes."""
        dev, n = device_of(arr)
        if dev:
            self.device = dev
        if n > self.n_devices:
            self.n_devices = n
        self.bytes_d2h = max(self.bytes_d2h, nbytes_of(arr))

    def fail(self, exc: BaseException) -> None:
        self.verdict = "raised"
        self.error = repr(exc)
        self.done()

    def done(self) -> None:
        if self._done:
            return
        self._done = True
        if self._restamp:
            # Completion stamp, not begin stamp: a first launch whose
            # jit compile outlives the watchdog window must not be born
            # outside it (the record would classify as idle the moment
            # it lands). _t0 keeps durations; wall/mono mean "landed".
            self.wall = time.time()
            self.mono = time.monotonic()
        try:
            _append(self._finalize())
        except Exception:  # pragma: no cover - recording never fatal
            pass

    def _finalize(self) -> dict:
        if not self.device:
            self.device = default_device_str()
        backend = (_backend.backend_label(self.device) if self.device
                   else "unknown")
        occ = (round(self.lanes / self.capacity, 4)
               if self.capacity else None)
        cc = None if self.compile_hit is None else \
            ("hit" if self.compile_hit else "miss")
        return {
            "wall": round(self.wall, 6),
            "mono": self.mono,
            "dur_ms": round((time.perf_counter() - self._t0) * 1e3, 4),
            "workload": self.workload,
            "kernel": self.kernel,
            "backend": backend,
            "device": self.device,
            "n_devices": self.n_devices,
            "lanes": self.lanes,
            "capacity": self.capacity,
            "occupancy": occ,
            "bytes_h2d": int(self.bytes_h2d),
            "bytes_d2h": int(self.bytes_d2h),
            "compile_cache": cc,
            "rows": self.rows,
            "stages_ms": dict(self.stages_ms),
            "shard_lanes": (list(self.shard_lanes)
                            if self.shard_lanes is not None else None),
            "active_devices": (list(self.active_devices)
                               if self.active_devices is not None
                               else None),
            "verdict": self.verdict or "ok",
            "ok_lanes": self.ok_lanes,
            "error": self.error,
        }


class _LaunchCtx:
    """with ledger.launch("general") as rec: — fail() on exception
    (exception propagates), done() otherwise."""

    __slots__ = ("_rec", "_token")

    def __init__(self, rec: LaunchRecord):
        self._rec = rec

    def __enter__(self) -> LaunchRecord:
        self._token = _OPEN.set(self._rec)
        return self._rec

    def __exit__(self, etype, exc, tb) -> bool:
        _OPEN.reset(self._token)
        if exc is not None:
            self._rec.fail(exc)
        else:
            self._rec.done()
        return False


def begin(kernel: str) -> LaunchRecord:
    """Open a record for a launch whose lifetime doesn't fit a single
    `with` block (verify.py pipelines chunk dispatch and readback)."""
    return LaunchRecord(kernel)


def launch(kernel: str) -> _LaunchCtx:
    return _LaunchCtx(begin(kernel))


def current() -> LaunchRecord | None:
    """The record of the enclosing `with launch(...)` block, if any."""
    return _OPEN.get()


def _append(record: dict) -> None:
    global _EVICTED
    evicted = False
    with _LOCK:
        if len(_RING) >= (_RING.maxlen or 0):
            _EVICTED += 1
            evicted = True
        _RING.append(record)
    try:
        from ...libs.metrics import tpu_metrics

        tmet = tpu_metrics()
        tmet.launch_ledger_records.inc(workload=record["workload"],
                                       backend=record["backend"])
        if evicted:
            tmet.launch_ledger_evictions.inc()
    except Exception:  # pragma: no cover - metrics never fatal
        pass


def record(**fields) -> None:
    """One-shot record for sites with nothing to time (tests, host
    degradations a caller wants ledger-visible)."""
    rec = LaunchRecord(fields.pop("kernel", "general"))
    if "mono" in fields or "wall" in fields:
        rec._restamp = False  # caller-pinned timestamps win
    for k, v in fields.items():
        setattr(rec, k, v)
    rec.done()


# ---------------------------------------------------------------- reads


def set_capacity(n: int) -> None:
    """Resize the ring (config crypto.ledger_capacity; node._build).
    Keeps the newest records; resets eviction count."""
    global _RING, _EVICTED
    n = max(int(n), 16)
    with _LOCK:
        if _RING.maxlen == n:
            return
        _RING = deque(_RING, maxlen=n)
        _EVICTED = 0


def capacity() -> int:
    return _RING.maxlen or 0


def evicted() -> int:
    return _EVICTED


def reset() -> None:
    """Test hook: drop every record, eviction count, and HBM entry."""
    global _EVICTED
    with _LOCK:
        _RING.clear()
        _EVICTED = 0
    with _HBM_LOCK:
        _HBM.clear()


def snapshot(workload: str | None = None,
             seconds: float | None = None) -> list[dict]:
    """Records oldest-first; optionally only one workload and/or only
    the last `seconds` (monotonic window)."""
    with _LOCK:
        recs = list(_RING)
    if seconds:
        cut = time.monotonic() - seconds
        recs = [r for r in recs if r["mono"] >= cut]
    if workload:
        recs = [r for r in recs if r["workload"] == workload]
    return recs


def _pctl(vals: list[float], p: float) -> float:
    if not vals:
        return 0.0
    vals = sorted(vals)
    return round(vals[min(len(vals) - 1, int(p * len(vals)))], 4)


def rollup(records: list[dict] | None = None,
           seconds: float | None = None) -> dict:
    """Per-workload cost attribution over the ring (or an explicit
    record list): launch count, lanes, bytes each way, backend mix,
    verdict mix, exec p50/p99 — the summary BENCH lines, the e2e run
    report, and /debug/launches embed."""
    if records is None:
        records = snapshot(seconds=seconds)
    workloads: dict[str, dict] = {}
    for r in records:
        w = workloads.setdefault(r["workload"], {
            "launches": 0, "lanes": 0, "bytes_h2d": 0, "bytes_d2h": 0,
            "backends": {}, "verdicts": {}, "rows_lanes": {},
            "_exec": []})
        w["launches"] += 1
        w["lanes"] += r.get("lanes", 0)
        if r.get("rows"):
            w["rows_lanes"][r["rows"]] = \
                w["rows_lanes"].get(r["rows"], 0) + r.get("lanes", 0)
        w["bytes_h2d"] += r.get("bytes_h2d", 0)
        w["bytes_d2h"] += r.get("bytes_d2h", 0)
        w["backends"][r["backend"]] = \
            w["backends"].get(r["backend"], 0) + 1
        w["verdicts"][r["verdict"]] = \
            w["verdicts"].get(r["verdict"], 0) + 1
        ex = r.get("stages_ms", {}).get("exec")
        if ex is not None:
            w["_exec"].append(ex)
    for w in workloads.values():
        ex = w.pop("_exec")
        w["exec_ms_p50"] = _pctl(ex, 0.50)
        w["exec_ms_p99"] = _pctl(ex, 0.99)
    return {
        "records": len(records),
        "capacity": capacity(),
        "evicted": _EVICTED,
        "workloads": workloads,
    }


# ------------------------------------------------------- HBM accounting

# (device, kind) -> resident bytes. Kinds: comb_tables (replicated
# expanded tables, per chip), table_shard (key-range-sharded block),
# arena (resident arena buffers), arena_shard (per-device mesh arena
# block). Owners re-register on rebuild; 0 unregisters.
_HBM_LOCK = threading.Lock()
_HBM: dict[tuple[str, str], int] = {}


def register_hbm(kind: str, device: str, nbytes: int) -> None:
    """A device-resident allocation (comb tables, arena shards,
    resident buffers) claims `nbytes` on `device` — exported as
    tpu_hbm_resident_bytes{device,kind} and checked against chip
    capacity by the watchdog."""
    key = (str(device), str(kind))
    with _HBM_LOCK:
        if nbytes:
            _HBM[key] = int(nbytes)
        else:
            _HBM.pop(key, None)
    try:
        from ...libs.metrics import tpu_metrics

        tpu_metrics().hbm_resident_bytes.set(
            int(nbytes), device=key[0], kind=key[1])
    except Exception:  # pragma: no cover - metrics never fatal
        pass


def hbm_snapshot() -> dict[str, dict[str, int]]:
    """{device: {kind: bytes}} of every registered resident
    allocation."""
    out: dict[str, dict[str, int]] = {}
    with _HBM_LOCK:
        for (dev, kind), n in _HBM.items():
            out.setdefault(dev, {})[kind] = n
    return out


def hbm_device_totals() -> dict[str, int]:
    return {dev: sum(kinds.values())
            for dev, kinds in hbm_snapshot().items()}
