"""The one size-or-deadline collector (crypto/collector.py) and the one
guarded general-kernel launch (crypto/batch.py) under the admission
and light planes: the weight rule of the cut, delivery of results and
exceptions, the backlog bound, and the launch ladder's counters. The
planes' own behaviour is in test_admission.py / test_light_serving.py;
no kernel is compiled here (tpu_verify.verify_batch is substituted).
"""

import asyncio

import numpy as np
import pytest

from tendermint_tpu.crypto import batch as cbatch
from tendermint_tpu.crypto.collector import BacklogFull, BatchCollector
from tendermint_tpu.crypto.ed25519 import Ed25519PrivKey, Ed25519PubKey
from tendermint_tpu.libs import failpoints, tracing
from tendermint_tpu.libs.metrics import crypto_metrics, tpu_metrics
from tendermint_tpu.libs.overload import OverloadController

QUEUE = "mempool.preverify"
KINDS = (tracing.register_kind("test.collector_wait"),
         tracing.register_kind("test.collector_flush"))


def run(coro):
    return asyncio.run(coro)


def _collector(run_batch, **kw):
    kw.setdefault("limit", 64)
    kw.setdefault("flush_ms", 20.0)
    kw.setdefault("controller", OverloadController())
    return BatchCollector(queue=QUEUE, run_batch=run_batch,
                          span_kinds=KINDS, **kw)


# --- the cut ---------------------------------------------------------------


@pytest.mark.parametrize("weights,batch_max,want", [
    # weight 1 is the admission rule: min(len(pending), batch_max)
    ([1, 1, 1, 1, 1], 2, [[0, 1], [2, 3], [4]]),
    ([1, 1, 1], 8, [[0, 1, 2]]),
    # weight len(plan) is the light rule: a cut never outweighs
    # batch_max (the plane's launches have a closed set of shapes) …
    ([2, 2, 2], 3, [[0], [1], [2]]),
    ([2, 1, 2, 1], 3, [[0, 1], [2, 3]]),
    # … but an item heavier than batch_max still goes, alone
    ([5, 1, 1], 3, [[0], [1, 2]]),
    ([1, 5, 1], 3, [[0], [1], [2]]),
    ([7], 3, [[0]]),
], ids=["unit-fills", "unit-deadline", "weighted", "weighted-fills",
        "oversize-first", "oversize-between", "oversize-only"])
def test_cut_takes_while_under_batch_max(weights, batch_max, want):
    batches = []

    def run_batch(items):
        batches.append(list(items))
        return [i * 10 for i in items]

    async def go():
        c = _collector(run_batch, batch_max=batch_max)
        try:
            return await asyncio.wait_for(asyncio.gather(
                *(c.submit(i, w) for i, w in enumerate(weights))), 10.0)
        finally:
            c.close()

    assert run(go()) == [i * 10 for i in range(len(weights))]
    assert batches == want


def test_exception_reaches_the_whole_batch_and_the_next_batch_flushes():
    calls = []

    def run_batch(items):
        calls.append(list(items))
        if len(calls) == 1:
            raise RuntimeError("verify died")
        return [True] * len(items)

    async def go():
        c = _collector(run_batch, batch_max=2, flush_ms=5.0)
        try:
            first = await asyncio.wait_for(asyncio.gather(
                c.submit("a"), c.submit("b"), return_exceptions=True), 10.0)
            assert c.depth() == 0
            second = await asyncio.wait_for(c.submit("c"), 10.0)
            return first, second
        finally:
            c.close()

    first, second = run(go())
    assert [type(e) for e in first] == [RuntimeError, RuntimeError]
    assert first[0] is first[1]
    assert second is True
    assert calls == [["a", "b"], ["c"]]


def test_run_batch_is_called_in_a_worker_thread_under_the_flush_span():
    import threading

    seen = {}

    def run_batch(items):
        seen["thread"] = threading.get_ident()
        seen["kind"] = tracing.TRACER.current().kind
        tracing.TRACER.current().set_attr("backend", "host")
        return items

    async def go():
        c = _collector(run_batch, batch_max=1)
        try:
            with tracing.TRACER.span(tracing.P2P_RECV_MSG):
                return await asyncio.wait_for(c.submit("x", 3), 10.0)
        finally:
            c.close()

    tracing.TRACER.clear()
    assert run(go()) == "x"
    assert seen["thread"] != threading.get_ident()
    assert seen["kind"] == KINDS[1]
    recs = {r[0]: r for r in tracing.TRACER.snapshot() if r[0] in KINDS}
    wait, flush = recs[KINDS[0]], recs[KINDS[1]]
    assert wait[2] == 0 and flush[2] == 0       # roots, not the request's
    assert wait[6]["lanes"] == 3 and wait[6]["cut"] == "full"
    assert flush[6] == {"lanes": 3, "backend": "host"}
    assert wait[4] + wait[5] <= flush[4]


# --- the backlog -----------------------------------------------------------


def test_backlog_counts_items_in_flight_and_refuses_the_newest():
    import threading

    gate = threading.Event()

    def run_batch(items):
        gate.wait(timeout=10.0)
        return items

    async def go():
        ctl = OverloadController()
        c = _collector(run_batch, batch_max=2, flush_ms=1.0, limit=3,
                       controller=ctl)
        assert QUEUE in ctl.evaluate()["queues"]
        tasks = [asyncio.ensure_future(c.submit(i)) for i in range(2)]
        for _ in range(400):
            await asyncio.sleep(0.005)
            if c._in_flight == 2:
                break
        tasks.append(asyncio.ensure_future(c.submit(2)))
        await asyncio.sleep(0)
        assert c.depth() == 3 and c.saturated()
        with pytest.raises(BacklogFull) as ei:
            await c.submit(3)
        assert (ei.value.depth, ei.value.limit) == (3, 3)
        gate.set()
        assert await asyncio.wait_for(asyncio.gather(*tasks), 10.0) \
            == [0, 1, 2]
        c.close()
        assert QUEUE not in ctl.evaluate()["queues"]

    run(go())


def test_close_cancels_parked_and_keeps_a_newer_registration():
    async def go():
        ctl = OverloadController()
        old = _collector(lambda items: items, batch_max=8,
                         flush_ms=30_000.0, controller=ctl)
        parked = asyncio.ensure_future(old.submit("p"))
        await asyncio.sleep(0.01)
        new = _collector(lambda items: items, batch_max=8, controller=ctl)
        old.close()
        with pytest.raises(asyncio.CancelledError):
            await parked
        assert old.depth() == 0
        # owner-checked: the replacement's gauge survives old.close()
        assert QUEUE in ctl.evaluate()["queues"]
        new.close()

    run(go())


# --- the guarded launch ----------------------------------------------------

SIGNER = Ed25519PrivKey.from_secret(b"collector-test-signer")


def _triples(n, bad=()):
    pub = SIGNER.pub_key().bytes()
    msgs = [b"lane-%d" % i for i in range(n)]
    sigs = [bytes(64) if i in bad else SIGNER.sign(m)
            for i, m in enumerate(msgs)]
    return [pub] * n, msgs, sigs


def _oracle(pubs, msgs, sigs, shapes=None):
    return np.array([Ed25519PubKey(p).verify_signature(m, s)
                     for p, m, s in zip(pubs, msgs, sigs)], bool)


def _raises(pubs, msgs, sigs):
    raise RuntimeError("device fell over")


def _all_false(pubs, msgs, sigs):
    return np.zeros(len(pubs), bool)


@pytest.mark.parametrize(
    "kernel,threshold,breaker_open,backend,attempts,fallbacks,opens", [
        (_oracle, 1, False, "device", 1, 0, False),
        # under the threshold: the host was the plan, not a fallback
        (_raises, 100, False, "host", 0, 0, False),
        # a raising launch is ONE host launch, and an attempt
        (_raises, 1, False, "host", 1, 1, True),
        (_all_false, 1, False, "host_recheck", 1, 1, True),
        (_raises, 1, True, "host", 0, 1, True),
    ], ids=["device", "under-threshold", "raises", "sentinel-miss",
            "breaker-open"])
def test_guarded_launch_ladder(monkeypatch, kernel, threshold,
                               breaker_open, backend, attempts,
                               fallbacks, opens):
    from tendermint_tpu.crypto.tpu import backend as tpu_backend
    from tendermint_tpu.crypto.tpu import verify as tpu_verify

    seen = []

    def launch(pubs, msgs, sigs, shapes=None):
        seen.append(len(pubs))
        return kernel(pubs, msgs, sigs)

    monkeypatch.setattr(tpu_verify, "verify_batch", launch)
    cbatch.reset_breakers()
    if breaker_open:
        cbatch.breaker("ed25519").record_failure()
    cm, tm = crypto_metrics(), tpu_metrics()
    dev_lanes = tpu_backend.platform()
    before = (cm.device_launches.value(), tm.host_fallbacks.value(),
              cm.batch_lanes.value(backend="host"),
              cm.batch_lanes.value(backend=dev_lanes))
    try:
        verdicts, got = cbatch.guarded_ed25519_launch(
            *_triples(4, bad={2}), workload="admission",
            device_threshold=threshold)
        assert got == backend
        assert verdicts.tolist() == [True, True, False, True]
        # the sentinel rides last and is stripped
        assert seen == ([5] if attempts and not breaker_open else [])
        assert cbatch.device_available("ed25519") is not opens
    finally:
        cbatch.reset_breakers()
    after = (cm.device_launches.value(), tm.host_fallbacks.value(),
             cm.batch_lanes.value(backend="host"),
             cm.batch_lanes.value(backend=dev_lanes))
    landed = backend in ("device", "host_recheck")
    assert [a - b for a, b in zip(after, before)] == [
        attempts, fallbacks, 4 if backend == "host" else 0,
        4 if landed else 0]

    class Launches:
        seen = []

        def inc(self, backend):
            self.seen.append(backend)

    cbatch.note_plane_launch(Launches(), got)
    # a re-checked device launch landed first: it counts as both
    assert Launches.seen == (["device", "host_recheck"]
                             if got == "host_recheck" else [got])


def test_device_verify_failpoint_fails_the_guarded_launch(monkeypatch):
    from tendermint_tpu.crypto.tpu import verify as tpu_verify

    monkeypatch.setattr(tpu_verify, "verify_batch", _oracle)
    cbatch.reset_breakers()
    failpoints.reset()
    failpoints.arm("device.verify", "error")
    try:
        verdicts, backend = cbatch.guarded_ed25519_launch(
            *_triples(2), workload="light", device_threshold=1)
        assert backend == "host" and verdicts.all()
        assert not cbatch.device_available("ed25519")
    finally:
        failpoints.reset()
        cbatch.reset_breakers()


# --- one host verify under both planes -------------------------------------


@pytest.mark.parametrize("plane", ["admission", "light"])
def test_wrong_length_signature_or_key_reads_false_on_the_host(plane):
    """crypto/ed25519.py: verify_signature answers False to a signature
    that is not 64 bytes and Ed25519PubKey refuses a key that is not
    32; the planes' one host verify reads both as an invalid lane."""
    pub = SIGNER.pub_key()
    msg = b"pinned"
    sig = SIGNER.sign(msg)
    assert pub.verify_signature(msg, sig[:63]) is False
    with pytest.raises(ValueError):
        Ed25519PubKey(pub.bytes()[:31])

    if plane == "admission":
        from tendermint_tpu.mempool.admission import AdmissionCollector
        from tendermint_tpu.types import tx_envelope as te

        def env(p, s):
            return te.TxEnvelope(p, s, msg)

        good = SIGNER.sign(te.sign_bytes(msg))

        async def go():
            c = AdmissionCollector(device_threshold=1 << 20,
                                   controller=OverloadController())
            try:
                return c._verify_batch([
                    env(pub.bytes(), good), env(pub.bytes(), good[:63]),
                    env(pub.bytes(), good + b"\0"),
                    env(pub.bytes()[:31], good)])
            finally:
                c.close()

        assert run(go()).tolist() == [True, False, False, False]
    else:
        from tendermint_tpu.light.serving import LightVerifyCollector

        async def go():
            c = LightVerifyCollector(device_threshold=1 << 20,
                                     controller=OverloadController())
            try:
                return c._verify_triples([
                    (pub, msg, sig), (pub, msg, sig[:63]),
                    (pub, msg, sig + b"\0")])
            finally:
                c.close()

        assert run(go()).tolist() == [True, False, False]
