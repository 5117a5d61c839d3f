"""Device-offloaded tx admission plane: batched ed25519 signature
pre-verification in front of CheckTx (ROADMAP item 3; no reference
equivalent — the reference pays a full ABCI round trip per tx).

Every tx entering the mempool — RPC ``broadcast_tx_*``, p2p gossip,
mempool-WAL replay — funnels through ``CListMempool.check_tx``, which
hands it to the AdmissionPlane here BEFORE the app sees it:

  * txs carrying a types/tx_envelope.py signature envelope are
    coalesced by a micro-batching collector (flush on size or
    deadline, like the consensus vote scheduler) into ONE wide
    ed25519 verify launch; only signature-valid txs proceed to the
    ABCI CheckTx round trip, the rest are shed with a counter and a
    deterministic reject — a garbage-signature flood dies at the
    device, not in the app;
  * unsigned txs pass through under ``mempool.admission=permissive``
    and are shed under ``strict``;
  * the pending+in-verify backlog is a tracked bounded queue
    (``mempool.preverify`` in the libs/overload.py QUEUES catalog):
    when full the NEWEST arrival is shed with a 429-style error, so a
    flood can never grow an unbounded verify backlog.

The collector is crypto/collector.py's (size-or-deadline flusher,
bounded backlog, verify in an executor thread — so a slow device, or
an armed ``mempool.admission.verify`` delay, backs up the bounded
queue and sheds instead of stalling the event loop), and a batch is
verified by crypto/batch.py's one guarded general-kernel launch:
breaker-aware, host oracle under the device crossover, a known-answer
sentinel lane on every device batch so that a wrong-verdict device
re-verifies on host instead of mass-rejecting possibly-valid txs,
while an honest all-garbage batch dies at the device. What lives here
is what is the admission plane's own: its failpoint, its metrics, its
shed accounting and the policy.
"""

from __future__ import annotations

import time

import numpy as np

from ..crypto.collector import BacklogFull, BatchCollector
from ..libs import tracing
from ..types import tx_envelope

PREVERIFY_QUEUE = "mempool.preverify"

# ResponseCheckTx.code for txs rejected at admission (deterministic,
# app never consulted). 429 on the nose: load generators distinguish
# "bad envelope, don't retry" from app-level rejects.
CODE_ADMISSION_REJECT = 429

# Shed reasons — the closed label set of admission_shed_total.
SHED_BAD_SIGNATURE = "bad_signature"
SHED_MALFORMED = "malformed"
SHED_UNSIGNED = "unsigned"
SHED_QUEUE_FULL = "queue_full"
SHED_REASONS = (SHED_BAD_SIGNATURE, SHED_MALFORMED, SHED_UNSIGNED,
                SHED_QUEUE_FULL)


class AdmissionQueueFullError(Exception):
    """Pre-verify backlog full: the newest tx is shed (429 at RPC) —
    transient backpressure, NOT a verdict on the tx itself."""

    def __init__(self, depth: int, limit: int):
        super().__init__(
            f"admission pre-verify queue full: {depth} txs pending "
            f"(limit {limit}); retry later")


class AdmissionCollector(BatchCollector):
    """Micro-batching signature-verify collector: the admission face
    of crypto/collector.py's BatchCollector. An item is one envelope,
    of weight 1 — batches cut at ``batch_max`` txs or ``flush_ms``
    after the first pending arrival — and a batch is verified by ONE
    guarded general-kernel launch (crypto/batch.py)."""

    def __init__(self, batch_max: int = 256, flush_ms: float = 2.0,
                 queue_max: int = 2048, device_threshold: int | None = None,
                 controller=None):
        from ..crypto import batch as cbatch

        self.device_threshold = cbatch._DEVICE_THRESHOLD \
            if device_threshold is None else device_threshold
        super().__init__(
            queue=PREVERIFY_QUEUE, limit=queue_max, batch_max=batch_max,
            flush_ms=flush_ms,
            # looked up at flush time: tests and the benchmark's
            # rehearsal replace _verify_batch on the instance or class
            run_batch=lambda envs: self._verify_batch(envs),
            span_kinds=(tracing.ADMISSION_QUEUE_WAIT,
                        tracing.ADMISSION_FLUSH),
            controller=controller)

    @property
    def queue_max(self) -> int:
        return self.limit

    async def verify(self, env: tx_envelope.TxEnvelope) -> bool:
        """Queue `env` for the next batch; returns its lane verdict.
        Raises AdmissionQueueFullError (shed-newest) when the backlog
        is at its bound."""
        from ..libs.metrics import admission_metrics

        try:
            return bool(await self.submit(env))
        except BacklogFull as e:
            self._controller.shed(PREVERIFY_QUEUE)
            admission_metrics().sheds.inc(reason=SHED_QUEUE_FULL)
            raise AdmissionQueueFullError(e.depth, e.limit) from None

    # -- the batched verify launch (executor thread) -------------------

    def _verify_batch(self, envs: list) -> np.ndarray:
        from ..crypto import batch as cbatch
        from ..libs import failpoints
        from ..libs.metrics import admission_metrics

        met = admission_metrics()
        n = len(envs)
        met.batch_lanes.observe(n)
        met.batch_occupancy.observe(n / self.batch_max)
        t0 = time.perf_counter()
        try:
            triples = ([e.pub_key for e in envs],
                       [tx_envelope.sign_bytes(e.payload) for e in envs],
                       [e.signature for e in envs])
            try:
                failpoints.hit("mempool.admission.verify")
            except failpoints.FailpointError:
                # injected launch failure: degrade to the host oracle,
                # exactly like a raising device launch
                verdicts, backend = cbatch.host_ed25519_launch(*triples)
            else:
                verdicts, backend = cbatch.guarded_ed25519_launch(
                    *triples, workload="admission",
                    device_threshold=self.device_threshold)
            cbatch.note_plane_launch(met.launches, backend)
            return verdicts
        finally:
            met.verify_seconds.observe(time.perf_counter() - t0)


class AdmissionPlane:
    """Policy wrapper the mempool calls per tx: parse the (optional)
    envelope, route enveloped txs through the collector, apply the
    permissive/strict unsigned policy, keep /status-visible tallies."""

    def __init__(self, config):
        self.mode = config.admission
        self.collector = AdmissionCollector(
            batch_max=config.admission_batch,
            flush_ms=config.admission_flush_ms,
            queue_max=config.admission_queue)
        # running tallies for the /status admission check (metric
        # counters mirror these with labels)
        self.admitted_signed = 0
        self.admitted_unsigned = 0
        self.sheds: dict[str, int] = {r: 0 for r in SHED_REASONS}

    def close(self) -> None:
        self.collector.close()

    def saturated(self) -> bool:
        return self.collector.saturated()

    def count_queue_full_shed(self) -> None:
        """Tally a queue_full shed decided OUTSIDE the collector (the
        check_tx / RPC admission_error preflights), so every shed
        moves the same counters no matter which guard caught it."""
        self._shed(SHED_QUEUE_FULL)

    def _shed(self, reason: str) -> str:
        from ..libs.metrics import admission_metrics

        self.sheds[reason] += 1
        admission_metrics().sheds.inc(reason=reason)
        return reason

    async def admit(self, tx: bytes) -> str | None:
        """None = proceed to CheckTx; a SHED_* reason string = reject
        deterministically before the app. Raises
        AdmissionQueueFullError when the pre-verify backlog sheds the
        tx (transient, 429 at RPC)."""
        from ..libs.metrics import admission_metrics

        try:
            env = tx_envelope.parse(tx)
        except tx_envelope.MalformedEnvelopeError:
            return self._shed(SHED_MALFORMED)
        if env is None:
            if self.mode == "strict":
                return self._shed(SHED_UNSIGNED)
            self.admitted_unsigned += 1
            admission_metrics().admitted.inc(signed="no")
            return None
        try:
            ok = await self.collector.verify(env)
        except AdmissionQueueFullError:
            # counted in the collector (queue_full); tally here too so
            # /status shows one coherent shed breakdown
            self.sheds[SHED_QUEUE_FULL] += 1
            raise
        if not ok:
            return self._shed(SHED_BAD_SIGNATURE)
        self.admitted_signed += 1
        admission_metrics().admitted.inc(signed="yes")
        return None

    # -- /status -------------------------------------------------------

    def status_check(self) -> dict:
        """The GET /status `admission` check body: mode, backlog fill,
        shed/admit tallies, verify-backend split. Shedding is designed
        behavior — only a saturated backlog degrades the check."""
        from ..crypto import batch as cbatch
        from ..libs.metrics import admission_metrics

        met = admission_metrics()
        depth = self.collector.depth()
        cap = self.collector.queue_max
        out: dict = {
            "mode": self.mode,
            "queue_depth": depth,
            "queue_capacity": cap,
            "admitted": {"signed": self.admitted_signed,
                         "unsigned": self.admitted_unsigned},
            "shed": {r: n for r, n in self.sheds.items() if n},
            "verify_launches": {
                b: int(met.launches.value(backend=b))
                for b in ("device", "host", "host_recheck")
                if met.launches.value(backend=b)},
        }
        fill = depth / cap if cap else 0.0
        if fill >= 0.8:
            out["status"] = "degraded"
            out["detail"] = (f"pre-verify backlog at {fill:.0%}; "
                             "shedding newest arrivals soon")
        else:
            out["status"] = "ok"
            if not cbatch.device_available("ed25519"):
                out["detail"] = ("ed25519 breaker open: admission "
                                 "verifying on host")
        return out
