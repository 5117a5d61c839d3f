"""BatchVerifier — the framework's new first-class capability.

The reference has no batch verifier anywhere (SURVEY §2.2): every
verification site calls the synchronous one-at-a-time
``PubKey.VerifySignature``. Here every consensus-critical site
(VoteSet.add_vote, ValidatorSet.verify_commit*, evidence, light client,
fast sync) funnels (pubkey, msg, sig) triples through this API, which
executes them as one wide device batch with per-lane verdicts.

Per-lane verdicts (not a single batch bool) are load-bearing: evidence
handling must know exactly which signature failed, and one bad vote
must not poison the verdicts of the others.

Tiny batches short-circuit to the host oracle — a device round trip is
not worth it under ``_DEVICE_THRESHOLD`` signatures.
"""

from __future__ import annotations

import functools
import hashlib
import logging
import threading

from ..libs import clock

import numpy as np

from . import PubKey
from .tpu import backend as _tpu_backend
from ..libs import tracing

logger = logging.getLogger("crypto.batch")

# Below this many sigs, host verification beats the device round trip.
# Derivation (docs/THRESHOLDS.md): device cost at small batches is the
# fixed launch term vs host OpenSSL ~0.15 ms/sig. The launch term has
# not been measured on a chip this process owns, so 40 is a design
# point, not a tuned crossover; tools/sweep_thresholds.py re-derives it.
_DEVICE_THRESHOLD = 40
# sr25519 has no OpenSSL fast path — the host oracle costs ~5.5 ms/sig
# (pure Python + SIMD Merlin), ~37x ed25519's — so its device
# crossover is a handful of lanes, not 40.
_DEVICE_THRESHOLD_SR = 4
# Degraded mode (accelerator down): batches at least this big route to
# the XLA-CPU-jitted sr25519 kernel instead of the ~5.5 ms/sig pure-
# Python oracle; smaller ones aren't worth a (cached) CPU compile.
_CPU_JIT_THRESHOLD_SR = 16

# Device-failure degradation: a kernel launch raising (OOM, backend
# death, NaN verdicts) opens a per-backend CIRCUIT
# BREAKER; every caller transparently gets host verdicts — identical
# semantics, just slower — instead of an exception on a consensus-
# critical path. Unlike the old flat 30 s cooldown (which retried by
# burning a full PRODUCTION batch every window), recovery is probed
# with a small SYNTHETIC batch: when the cooldown expires the breaker
# goes half-open and the next would-be device caller runs a
# PROBE_LANES-sized known-answer batch first — a still-dead device
# costs one probe per window and a production commit batch never hits
# an open breaker. Cooldowns grow exponentially with jitter so a
# persistently broken backend backs off instead of probing in
# lockstep across the fleet.
BREAKER_BASE_COOLDOWN_S = 2.0
BREAKER_MAX_COOLDOWN_S = 300.0
PROBE_LANES = 8                 # synthetic lanes per half-open probe

CLOSED, OPEN, HALF_OPEN = "closed", "open", "half_open"
_STATE_CODE = {CLOSED: 0, OPEN: 1, HALF_OPEN: 2}


class CircuitBreaker:
    """closed -> (launch raised) -> open -> (cooldown expired, next
    acquire) -> half-open probe -> closed on success, open again (with
    a doubled cooldown) on failure. Thread-safe: BatchVerifier runs in
    executor threads; only one caller probes at a time and concurrent
    acquirers during a probe take the host path instead of blocking."""

    def __init__(self, backend: str, probe):
        self.backend = backend
        self._label = backend  # log/metric identity; subclasses extend
        self._probe = probe  # () -> bool: synthetic batch round trip
        self._lock = threading.Lock()
        self.state = CLOSED
        self.consecutive_failures = 0
        self._open_until = 0.0
        self._probing = False

    # -- reads --

    def available(self) -> bool:
        """Pure read: True iff closed (health checks, expanded-path
        gating). Never probes."""
        return self.state == CLOSED

    def cooldown_remaining(self) -> float:
        if self.state == CLOSED:
            return 0.0
        return max(0.0, self._open_until - clock.monotonic())

    # -- transitions --

    def _set_state(self, state: str) -> None:
        self.state = state
        try:
            from ..libs.metrics import crypto_metrics

            crypto_metrics().breaker_state.set(
                _STATE_CODE[state], backend=self.backend)
        except Exception:  # pragma: no cover - metrics never fatal
            pass

    def _count_open(self) -> None:
        from ..libs.metrics import crypto_metrics

        crypto_metrics().breaker_opens.inc(backend=self.backend)

    def _open_locked(self) -> None:
        from ..libs.net import jittered_backoff

        cd = jittered_backoff(max(self.consecutive_failures - 1, 0),
                              BREAKER_BASE_COOLDOWN_S,
                              BREAKER_MAX_COOLDOWN_S)
        self._open_until = clock.monotonic() + cd
        self._set_state(OPEN)
        self._count_open()
        logger.warning(
            "device breaker OPEN (%s): failure #%d, cooldown %.1fs",
            self._label, self.consecutive_failures, cd)

    def record_failure(self) -> None:
        """A production (or probe) launch raised on this backend."""
        with self._lock:
            self.consecutive_failures += 1
            self._open_locked()

    def acquire(self) -> bool:
        """Called by verify paths before launching on device. Closed:
        go ahead. Open and cooling down: host path. Open and expired:
        half-open — run the synthetic probe inline (bounded, probe-
        sized); success closes the breaker and admits the caller."""
        with self._lock:
            if self.state == CLOSED:
                return True
            if self._probing or clock.monotonic() < self._open_until:
                return False
            self._probing = True
            self._set_state(HALF_OPEN)
        ok = False
        try:
            ok = bool(self._probe())
        except Exception:
            logger.exception("half-open probe raised (%s)", self._label)
            ok = False
        from ..libs.metrics import crypto_metrics

        crypto_metrics().breaker_probes.inc(
            backend=self.backend, result="ok" if ok else "failed")
        with self._lock:
            self._probing = False
            if ok:
                self.consecutive_failures = 0
                self._set_state(CLOSED)
                logger.warning(
                    "device breaker CLOSED (%s): probe succeeded",
                    self._label)
            else:
                self.consecutive_failures += 1
                self._open_locked()
        return ok

    def reset(self) -> None:
        with self._lock:
            self.consecutive_failures = 0
            self._open_until = 0.0
            self._probing = False
            self._set_state(CLOSED)


@functools.cache
def _ed_probe_triple() -> tuple[bytes, bytes, bytes]:
    from . import ed25519_ref as edr

    seed = hashlib.sha256(b"tendermint_tpu ed25519 breaker probe").digest()
    msg = b"breaker probe"
    return edr.public_key_from_seed(seed), msg, edr.sign(seed, msg)


def _probe_ed25519() -> bool:
    from ..libs import failpoints
    from .tpu import ledger as tpu_ledger
    from .tpu import verify as tpu_verify

    failpoints.hit("device.verify")
    p, m, s = _ed_probe_triple()
    with tpu_ledger.workload("probe"):
        out = tpu_verify.verify_batch(
            [p] * PROBE_LANES, [m] * PROBE_LANES, [s] * PROBE_LANES)
    # a NaN-ing kernel returns wrong verdicts without raising — a
    # known-answer mismatch is a failed probe, not a closed breaker
    return bool(np.asarray(out).all())


@functools.cache
def _sr_probe_triple() -> tuple[bytes, bytes, bytes]:
    from . import sr25519_ref as srr

    mini = hashlib.sha256(b"tendermint_tpu sr25519 breaker probe").digest()
    msg = b"breaker probe"
    return srr.public_key_from_mini(mini), msg, srr.sign(mini, msg)


def _probe_sr25519() -> bool:
    from ..libs import failpoints
    from .tpu import ledger as tpu_ledger
    from .tpu import sr_verify

    failpoints.hit("device.verify")
    p, m, s = _sr_probe_triple()
    with tpu_ledger.workload("probe"):
        out = sr_verify.verify_batch_sr(
            [p] * PROBE_LANES, [m] * PROBE_LANES, [s] * PROBE_LANES)
    return bool(np.asarray(out).all())


_BREAKERS: dict[str, CircuitBreaker] = {
    "ed25519": CircuitBreaker("ed25519", _probe_ed25519),
    "sr25519": CircuitBreaker("sr25519", _probe_sr25519),
}

_BACKEND_PROBES = {"ed25519": _probe_ed25519, "sr25519": _probe_sr25519}


class DeviceBreaker(CircuitBreaker):
    """Per-mesh-device breaker UNDER the per-backend one: a chip that
    raises or returns wrong verdicts is evicted alone (its breaker
    opens, the fabric reshards over the survivors) while the backend
    breaker stays closed and every other chip keeps serving. The
    half-open probe is the same PROBE_LANES known-answer batch, pinned
    to THIS device via jax.default_device — a passing probe re-admits
    the chip and the next dispatch reshards back to full width.
    Backend-wide semantics are preserved by mark_device_failed(): when
    every mesh device is open, the backend breaker opens too."""

    def __init__(self, backend: str, device: str):
        super().__init__(backend, None)
        self.device = device
        self._label = f"{backend} {device}"
        self._probe = self._device_probe

    def _set_state(self, state: str) -> None:
        self.state = state
        try:
            from ..libs.metrics import tpu_metrics

            tpu_metrics().device_breaker_state.set(
                _STATE_CODE[state], device=self.device)
        except Exception:  # pragma: no cover - metrics never fatal
            pass

    def _count_open(self) -> None:
        # device evictions are counted by mark_device_failed()
        # (tpu_mesh_evictions_total{device,reason}); the per-backend
        # crypto_breaker_opens_total stays backend-wide-only.
        pass

    def _device_probe(self) -> bool:
        import jax

        dev = next((d for d in jax.devices()
                    if str(d) == self.device), None)
        if dev is None:
            return False
        probe = _BACKEND_PROBES[self.backend]
        # The probe's 8 lanes pad below the shard crossover, so it
        # launches single-device — pinning the default device makes it
        # a round trip through THIS chip only. A recursive
        # evicted_devices(probe=True) during the probe sees
        # self._probing and keeps the device listed as evicted.
        with jax.default_device(dev):
            return bool(probe())


# (backend, full device string) -> DeviceBreaker; created lazily on
# first eviction so a mesh-less process never mints device state.
_DEVICE_BREAKERS: dict[tuple[str, str], DeviceBreaker] = {}
_DEVICE_LOCK = threading.Lock()


def device_breaker(backend: str, device: str) -> DeviceBreaker:
    with _DEVICE_LOCK:
        br = _DEVICE_BREAKERS.get((backend, device))
        if br is None:
            br = _DEVICE_BREAKERS[(backend, device)] = DeviceBreaker(
                backend, device)
        return br


def device_breaker_states(backend: str | None = None) -> dict[str, str]:
    """{device: state} for the /status device check (all backends
    merged unless one is named)."""
    with _DEVICE_LOCK:
        return {dev: br.state
                for (be, dev), br in sorted(_DEVICE_BREAKERS.items())
                if backend is None or be == backend}


def evicted_devices(backend: str = "ed25519",
                    probe: bool = False) -> list[str]:
    """Sorted full device strings whose per-device breaker is not
    closed. probe=False is a pure read (watchdog, /status — must never
    launch); probe=True additionally runs any DUE half-open per-device
    probes inline, so dispatch entry points both learn the surviving
    set and drive re-admission."""
    with _DEVICE_LOCK:
        brs = [br for (be, _), br in _DEVICE_BREAKERS.items()
               if be == backend]
    out = []
    readmitted = False
    for br in brs:
        if probe and not br.available():
            br.acquire()  # no-op while cooling down / already probing
            if br.available():
                readmitted = True
        if not br.available():
            out.append(br.device)
    if readmitted:
        _set_active_devices(backend)
    return sorted(out)


def readmit_device(backend: str, device: str) -> None:
    """Force a device's breaker closed without a probe — the operator
    override (and the deterministic sim/scenario hook; the natural
    path is a passing half-open probe via evicted_devices(probe=True))."""
    with _DEVICE_LOCK:
        br = _DEVICE_BREAKERS.get((backend, device))
    if br is not None:
        br.reset()
        logger.warning("mesh device %s force re-admitted (%s backend)",
                       device, backend)
    _set_active_devices(backend)


def _mesh_device_strs() -> list[str]:
    """Full device strings of the (undegraded) verify mesh; [] when no
    multi-device mesh exists or jax never came up."""
    import sys

    if "jax" not in sys.modules:  # pure read: never trigger bring-up
        return []
    try:
        from .tpu import verify as tpu_verify

        mesh = tpu_verify._mesh()
    except Exception:  # pragma: no cover - backend bring-up failed
        return []
    if mesh is None:
        return []
    return [str(d) for d in mesh.devices.flat]


def _set_active_devices(backend: str = "ed25519") -> None:
    devs = _mesh_device_strs()
    if not devs:
        return
    try:
        from ..libs.metrics import tpu_metrics

        evicted = set(evicted_devices(backend))
        tpu_metrics().mesh_active_devices.set(
            len([d for d in devs if d not in evicted]))
    except Exception:  # pragma: no cover - metrics never fatal
        pass


def breaker(backend: str = "ed25519") -> CircuitBreaker:
    return _BREAKERS[backend]


def breaker_states() -> dict[str, str]:
    """{backend: state} — the /status device check detail."""
    return {name: b.state for name, b in _BREAKERS.items()}


def reset_breakers() -> None:
    """Test hook: force every backend AND device breaker closed."""
    for b in _BREAKERS.values():
        b.reset()
    with _DEVICE_LOCK:
        device_brs = list(_DEVICE_BREAKERS.values())
        _DEVICE_BREAKERS.clear()
    for b in device_brs:
        b.reset()


# The silicon watchdog (crypto/tpu/watchdog.py — jax-free) reports
# mesh_degraded off this pure read (no probes, no bring-up);
# registering here keeps the dependency one-directional.
try:
    from .tpu import watchdog as _watchdog

    _watchdog.register_evicted_supplier(
        lambda: evicted_devices("ed25519", probe=False))
except Exception:  # pragma: no cover - watchdog import never fatal
    pass


# Host-only override (tendermint_tpu/sim): a deterministic simulation
# pins every verification to the host oracle — per-lane verdicts are
# a pure function of the inputs with no device runtime in the loop —
# unless the scenario explicitly exercises the device verifier.
_FORCE_HOST = False


def set_force_host(on: bool) -> bool:
    """Pin batch verification to the host path (returns the previous
    setting so callers can restore it)."""
    global _FORCE_HOST
    prev = _FORCE_HOST
    _FORCE_HOST = bool(on)
    return prev


def host_forced() -> bool:
    return _FORCE_HOST


def device_available(backend: str | None = None) -> bool:
    """Pure read (never probes): is the backend's breaker closed? With
    no backend, True only when EVERY breaker is closed (the legacy
    any-cooldown-engaged reading)."""
    if backend is not None:
        return _BREAKERS[backend].available()
    return all(b.available() for b in _BREAKERS.values())


def mark_device_failed(backend: str = "ed25519",
                       device=None, reason: str = "launch_error") -> None:
    """Record a device-side verify failure.

    With no `device`, the failure is backend-wide (a raising launch
    with no shard attribution): the backend breaker opens and every
    verify takes the host path until a probe passes — the PR-3
    semantics, unchanged.

    With `device` (a full device string, or a sequence of them — e.g.
    from MeshResidentArena.failed_shards()), only the NAMED chips'
    per-device breakers open: the fabric reshards over the survivors
    and keeps serving on silicon. Backend-wide semantics are preserved
    as the limit case — when every mesh device is open, the backend
    breaker opens too."""
    from ..libs.metrics import crypto_metrics

    crypto_metrics().device_failures.inc()
    if not device:
        _BREAKERS[backend].record_failure()
        return
    names = [device] if isinstance(device, str) else list(device)
    for name in names:
        device_breaker(backend, name).record_failure()
        try:
            from ..libs.metrics import tpu_metrics

            tpu_metrics().mesh_evictions.inc(device=name, reason=reason)
        except Exception:  # pragma: no cover - metrics never fatal
            pass
        logger.error("mesh device %s evicted (%s backend, reason=%s); "
                     "resharding fabric over survivors", name, backend,
                     reason)
    mesh_devs = _mesh_device_strs()
    if mesh_devs and set(evicted_devices(backend)) >= set(mesh_devs):
        # every chip is out — that IS a backend-wide failure
        logger.error("all %d mesh devices evicted (%s backend); "
                     "opening the backend breaker", len(mesh_devs),
                     backend)
        _BREAKERS[backend].record_failure()
    _set_active_devices(backend)


class BatchVerifier:
    """Accumulate signatures, verify them all at once.

    Usage:
        bv = BatchVerifier()
        bv.add(pk, msg, sig)   # any supported key type, mixed freely
        all_ok, lane_ok = bv.verify()
    """

    def __init__(self, use_device: bool | None = None):
        self._items: list[tuple[PubKey, bytes, bytes]] = []
        self._use_device = use_device

    def __len__(self) -> int:
        return len(self._items)

    def add(self, pub_key: PubKey, msg: bytes, sig: bytes) -> None:
        self._items.append((pub_key, msg, sig))

    def verify(self) -> tuple[bool, np.ndarray]:
        """Returns (all_valid, per-lane verdicts in add order)."""
        from ..libs.metrics import crypto_metrics

        m = crypto_metrics()
        n = len(self._items)
        if n == 0:
            return True, np.zeros(0, bool)
        verdicts = np.zeros(n, bool)
        with m.batch_seconds.time(), \
                tracing.TRACER.span(tracing.CRYPTO_BATCH, lanes=n):
            # Group lanes by key type; each goes through its backend.
            by_type: dict[str, list[int]] = {}
            for i, (pk, _, _) in enumerate(self._items):
                by_type.setdefault(pk.type_name, []).append(i)
            for type_name, idxs in by_type.items():
                items = [self._items[i] for i in idxs]
                group = self._verify_group(type_name, items)
                verdicts[np.asarray(idxs)] = group
        bad = int(n - verdicts.sum())
        if bad:
            m.invalid_sigs.inc(bad)
        return bool(verdicts.all()), verdicts

    def _verify_group(self, type_name, items) -> np.ndarray:
        from ..libs.metrics import crypto_metrics

        met = crypto_metrics()
        if type_name == "ed25519":
            use_dev = self._use_device
            if use_dev is None:
                use_dev = (not _FORCE_HOST
                           and len(items) >= _DEVICE_THRESHOLD)
            if use_dev and breaker("ed25519").acquire():
                try:
                    from ..libs import failpoints
                    from .tpu import verify as tpu_verify

                    failpoints.hit("device.verify")
                    met.device_launches.inc()
                    out = tpu_verify.verify_batch(
                        [pk.bytes() for pk, _, _ in items],
                        [m for _, m, _ in items],
                        [s for _, _, s in items],
                    )
                    met.batch_lanes.inc(
                        len(items), backend=_tpu_backend.platform())
                    return out
                except Exception:
                    mark_device_failed("ed25519")
                    logger.exception(
                        "device ed25519 batch failed (%d lanes); "
                        "breaker open %.1fs, degrading to host",
                        len(items),
                        breaker("ed25519").cooldown_remaining())
            if use_dev:
                # device wanted (threshold met) but unavailable/failed
                from ..libs.metrics import tpu_metrics

                tpu_metrics().host_fallbacks.inc()
            met.batch_lanes.inc(len(items), backend="host")
            # Host path: the per-key OpenSSL fast path (strict-accept ->
            # accept; reject -> ZIP-215 oracle recheck, crypto/ed25519.py).
            with tracing.TRACER.span(tracing.CRYPTO_HOST_VERIFY,
                                     lanes=len(items), backend="host"):
                return np.fromiter(
                    (
                        len(s) == 64 and pk.verify_signature(m, s)
                        for pk, m, s in items
                    ),
                    bool,
                    count=len(items),
                )
        if type_name == "sr25519":
            use_dev = self._use_device
            if use_dev is None:
                use_dev = (not _FORCE_HOST
                           and len(items) >= _DEVICE_THRESHOLD_SR)
            if use_dev and breaker("sr25519").acquire():
                try:
                    from ..libs import failpoints
                    from .tpu import sr_verify

                    failpoints.hit("device.verify")
                    met.device_launches.inc()
                    out = sr_verify.verify_batch_sr(
                        [pk.bytes() for pk, _, _ in items],
                        [m for _, m, _ in items],
                        [s for _, _, s in items],
                    )
                    met.batch_lanes.inc(
                        len(items),
                        backend=f"{_tpu_backend.platform()}-sr25519")
                    return out
                except Exception:
                    mark_device_failed("sr25519")
                    logger.exception(
                        "device sr25519 batch failed (%d lanes); "
                        "breaker open %.1fs, degrading to host",
                        len(items),
                        breaker("sr25519").cooldown_remaining())
            if use_dev:
                from ..libs.metrics import tpu_metrics

                tpu_metrics().host_fallbacks.inc()
            # Degraded-mode fast path: the same kernel pinned to the
            # XLA CPU backend. The pure-Python oracle costs ~5.5
            # ms/sig — a device outage on an sr25519-heavy chain would
            # take ~55 s per 10k commit; the CPU-jitted kernel keeps
            # degraded commits at sane cadence (VERDICT r4 ask #7).
            # (use_dev: only when the caller WANTED the device — an
            # explicit use_device=False keeps the per-sig oracle.)
            if use_dev and len(items) >= _CPU_JIT_THRESHOLD_SR:
                try:
                    from .tpu import sr_verify

                    out = sr_verify.verify_batch_sr(
                        [pk.bytes() for pk, _, _ in items],
                        [m for _, m, _ in items],
                        [s for _, _, s in items],
                        cpu=True,
                    )
                    met.batch_lanes.inc(len(items),
                                        backend="cpu-jit-sr25519")
                    return out
                except Exception:
                    logger.exception(
                        "CPU-jit sr25519 batch failed (%d lanes); "
                        "falling back to per-sig host oracle",
                        len(items))
        met.batch_lanes.inc(len(items), backend=f"host-{type_name}")
        # Remaining key types (secp256k1; small sr25519 groups):
        # host-side one-by-one via the PubKey objects we already hold.
        with tracing.TRACER.span(tracing.CRYPTO_HOST_VERIFY,
                                 lanes=len(items),
                                 backend=f"host-{type_name}"):
            return np.fromiter(
                (pk.verify_signature(m, s) for pk, m, s in items),
                bool,
                count=len(items),
            )


# -- the guarded general-kernel launch of the verify planes -----------
#
# The mempool admission plane and the light serving plane each cut
# batches of raw ed25519 triples that mix keys of any origin, so they
# launch the general kernel (per-lane keys), not one validator set's
# expanded tables. How such a launch is made safely is decided here,
# once. It is deliberately NOT BatchVerifier._verify_group's ed25519
# branch: the planes' policy differs (known-answer sentinel lane,
# host_recheck on a suspect verdict, no _FORCE_HOST pin, no use_device
# override), but the crypto/tpu device-health counters are the shared
# ones, so dashboards and the docs/CHAOS.md triage flow see the
# planes' launches next to consensus ones. Invalid lanes stay OUT of
# crypto_invalid_sigs on purpose: a garbage flood at admission is
# expected bulk (admission_shed_total{bad_signature}) and must not
# fire consensus invalid-signature alarms.


def note_plane_launch(launches, backend: str) -> None:
    """What a plane records of a launch that answered `backend`: its
    own `launches{backend}` counter — a device launch whose sentinel
    failed LANDED before its host re-check, so it counts as both — and
    the answer on the batch's flush span (the current one: the
    collector hands it to the worker thread)."""
    if backend == "host_recheck":
        launches.inc(backend="device")
    launches.inc(backend=backend)
    (tracing.TRACER.current() or tracing.NOOP_SPAN).set_attr(
        "backend", backend)


def _host_verify_ed25519(pubs, msgs, sigs) -> np.ndarray:
    """Per-key host verify of raw triples. A lane never raises: a
    wrong-length key (Ed25519PubKey refuses it), a wrong-length
    signature (tested here, and again by verify_signature) or any
    other exception reads False."""
    from .ed25519 import Ed25519PubKey

    out = np.zeros(len(pubs), bool)
    for i, (p, m, s) in enumerate(zip(pubs, msgs, sigs)):
        try:
            out[i] = len(s) == 64 and \
                Ed25519PubKey(p).verify_signature(m, s)
        except Exception:
            out[i] = False
    return out


def host_ed25519_launch(pubs, msgs, sigs) -> tuple[np.ndarray, str]:
    """ONE host launch of raw ed25519 triples, counted as such — the
    foot of the guarded ladder, and what a plane's own injected launch
    failure degrades to. Returns (verdicts, "host")."""
    from ..libs.metrics import crypto_metrics

    crypto_metrics().batch_lanes.inc(len(pubs), backend="host")
    return _host_verify_ed25519(pubs, msgs, sigs), "host"


def load_ed25519_programs(shapes) -> int:
    """Load the general program at a plane's closed set of launch
    shapes (crypto/tpu/verify.py LaunchShapes) before the plane takes
    its first request, so that nothing it launches afterwards compiles.
    Returns the programs loaded; 0, with nothing touched, where
    verification is pinned to the host or the breaker is open (the
    first device launch then compiles, as it always did)."""
    if _FORCE_HOST or not device_available("ed25519"):
        return 0
    from .tpu import ledger as tpu_ledger
    from .tpu import verify as tpu_verify

    with tpu_ledger.workload("probe"):
        return tpu_verify.load_general(shapes)


def guarded_ed25519_launch(pubs, msgs, sigs, workload: str,
                           device_threshold: int, shapes=None
                           ) -> tuple[np.ndarray, str]:
    """Verify raw ed25519 triples in one launch: on the device when
    the batch reaches `device_threshold` lanes and the breaker admits
    it, on the host oracle otherwise. `workload` tags the launch in
    the ledger (crypto/tpu/ledger.py). `shapes`: the plane's closed
    set of launch shapes (LaunchShapes), the sentinel's lane counted
    in; without it the launch takes the ladder of lane buckets.
    Returns (per-lane verdicts, backend), backend one of "device",
    "host", "host_recheck"."""
    from ..libs import failpoints
    from ..libs.metrics import crypto_metrics, tpu_metrics

    n = len(pubs)
    want_dev = n >= device_threshold
    if want_dev and breaker("ed25519").acquire():
        try:
            from .tpu import ledger as tpu_ledger
            from .tpu import verify as tpu_verify

            failpoints.hit("device.verify")
            # device_launches counts ATTEMPTS (the BatchVerifier
            # convention: a raising launch still burned a launch
            # slot); the lane count, and the caller's own launch
            # counter, land only after the launch returns, so a
            # raising launch falls through as ONE host launch, never
            # device+host for the same flush
            crypto_metrics().device_launches.inc()
            # one extra known-answer sentinel lane rides every batch,
            # last (the breaker probe's triple): a NaN-ing kernel
            # fails the sentinel, so a suspect verdict is detected
            # POSITIVELY — an honest all-garbage batch (sentinel
            # verifies, every real lane invalid) is trusted and dies
            # at the device, never paying a per-signature host
            # re-check
            spub, smsg, ssig = _ed_probe_triple()
            with tpu_ledger.workload(workload):
                out = np.asarray(tpu_verify.verify_batch(
                    [*pubs, spub], [*msgs, smsg], [*sigs, ssig],
                    shapes=shapes), bool)
            crypto_metrics().batch_lanes.inc(
                n, backend=_tpu_backend.platform())
            if out[-1]:
                return out[:n], "device"
            # sentinel mismatch: wrong-verdict device (the shape the
            # breaker's half-open probe exists for) — open the breaker
            # and re-verify on host rather than answering from
            # verdicts that may reject what is valid
            mark_device_failed("ed25519")
            logger.error(
                "%s device batch (%d lanes) failed its known-answer "
                "sentinel; breaker open %.1fs, re-verifying on host",
                workload, n, breaker("ed25519").cooldown_remaining())
            tpu_metrics().host_fallbacks.inc()
            return _host_verify_ed25519(pubs, msgs, sigs), "host_recheck"
        except Exception:
            mark_device_failed("ed25519")
            logger.exception(
                "%s device batch failed (%d lanes); breaker open "
                "%.1fs, degrading to host", workload, n,
                breaker("ed25519").cooldown_remaining())
    if want_dev:
        # device wanted (threshold met) but breaker-refused or raised:
        # same fallback signal as BatchVerifier._verify_group — never
        # for a batch under the threshold
        tpu_metrics().host_fallbacks.inc()
    return host_ed25519_launch(pubs, msgs, sigs)
