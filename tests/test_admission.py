"""Mempool admission plane: batched ed25519 signature
pre-verification in front of CheckTx (ISSUE 6).

Covers the tx_envelope codec, the micro-batch collector's edge cases
(deadline flush, size-vs-deadline race, shed-newest on a full
pre-verify queue, breaker-open host fallback, known-answer sentinel
lane → host re-verify on mismatch), the TxCache poisoning pin, WAL-replay
re-admission, the `mempool.admission.verify` failpoint shapes, and
the in-process acceptance flood: garbage-signature txs are FULLY shed
with zero app CheckTx calls while interleaved validly signed txs are
admitted in multi-lane batches.
"""

import asyncio
import threading
import time

import numpy as np
import pytest

from tendermint_tpu.abci import types as abci
from tendermint_tpu.abci.client import LocalClient
from tendermint_tpu.abci.kvstore import KVStoreApp
from tendermint_tpu.config import MempoolConfig
from tendermint_tpu.crypto import batch as cbatch
from tendermint_tpu.crypto.ed25519 import Ed25519PrivKey, Ed25519PubKey
from tendermint_tpu.libs import failpoints as fp
from tendermint_tpu.libs.metrics import admission_metrics
from tendermint_tpu.mempool.admission import (
    CODE_ADMISSION_REJECT, AdmissionCollector, AdmissionQueueFullError,
)
from tendermint_tpu.mempool.clist_mempool import CListMempool
from tendermint_tpu.types import tx_envelope


def run(coro):
    return asyncio.run(coro)


SIGNER = Ed25519PrivKey.from_secret(b"admission-test-signer")


def signed_tx(payload: bytes) -> bytes:
    return tx_envelope.sign_tx(SIGNER, payload)


def garbage_tx(payload: bytes) -> bytes:
    """Structurally valid envelope, hopeless signature."""
    return tx_envelope.encode(SIGNER.pub_key().bytes(), bytes(64), payload)


class CountingApp(KVStoreApp):
    """Counts CheckTx deliveries — the acceptance bar is that shed
    txs cost the app ZERO of these."""

    def __init__(self):
        super().__init__()
        self.check_calls = 0
        self.checked: list[bytes] = []

    def check_tx(self, req):
        self.check_calls += 1
        self.checked.append(req.tx)
        return super().check_tx(req)


def make_pool(app=None, **cfg):
    cfg.setdefault("admission", "permissive")
    cfg.setdefault("admission_batch", 16)
    cfg.setdefault("admission_flush_ms", 10.0)
    app = app or CountingApp()
    pool = CListMempool(MempoolConfig(**cfg), LocalClient(app))
    return pool, app


# --- codec ---------------------------------------------------------------


def test_envelope_roundtrip_and_detection():
    raw = signed_tx(b"payload-1")
    assert tx_envelope.is_enveloped(raw)
    env = tx_envelope.parse(raw)
    assert env.payload == b"payload-1"
    assert env.pub_key == SIGNER.pub_key().bytes()
    assert Ed25519PubKey(env.pub_key).verify_signature(
        tx_envelope.sign_bytes(env.payload), env.signature)
    # unsigned txs parse to None, untouched
    assert tx_envelope.parse(b"key=value") is None
    assert not tx_envelope.is_enveloped(b"key=value")


def test_envelope_malformed_is_reject_not_passthrough():
    # magic + garbage body must be MALFORMED (strict-mode bypass guard)
    for bad in (tx_envelope.MAGIC + b"\xff\xff",
                tx_envelope.MAGIC,  # missing all fields
                # wrong pubkey size
                tx_envelope.MAGIC + __import__(
                    "tendermint_tpu.encoding.proto",
                    fromlist=["Writer"]).Writer().finish()):
        with pytest.raises(tx_envelope.MalformedEnvelopeError):
            tx_envelope.parse(bad)
    with pytest.raises(ValueError):
        tx_envelope.encode(b"short", bytes(64), b"p")


# --- policy: permissive / strict / malformed ----------------------------


def test_unsigned_passthrough_permissive_shed_strict():
    async def go():
        pool, app = make_pool()
        res = await pool.check_tx(b"plain-tx")
        assert res.code == abci.CODE_TYPE_OK and app.check_calls == 1
        pool.close()

        pool2, app2 = make_pool(admission="strict")
        res = await pool2.check_tx(b"plain-tx")
        assert res.code == CODE_ADMISSION_REJECT
        assert "unsigned" in res.log
        assert app2.check_calls == 0
        assert pool2.admission.sheds["unsigned"] == 1
        # signed txs still flow under strict
        res = await pool2.check_tx(signed_tx(b"s1"))
        assert res.code == abci.CODE_TYPE_OK and app2.check_calls == 1
        pool2.close()

    run(go())


def test_malformed_envelope_shed_before_app():
    async def go():
        pool, app = make_pool()
        res = await pool.check_tx(tx_envelope.MAGIC + b"\x01garbage")
        assert res.code == CODE_ADMISSION_REJECT
        assert "malformed" in res.log
        assert app.check_calls == 0
        pool.close()

    run(go())


# --- acceptance: the flood dies at the device, not in the app -----------


def test_garbage_flood_fully_shed_zero_abci_calls(monkeypatch):
    """ISSUE 6 acceptance: a garbage-signature flood is FULLY shed at
    admission with ZERO ABCI CheckTx calls for the shed txs, while
    interleaved validly signed txs are admitted in batches of >1
    (batch-lanes/occupancy metrics observed) through the DEVICE
    backend (kernel faked — verdicts computed by the host oracle — so
    the test exercises the device code path without a compile)."""
    from tendermint_tpu.crypto.tpu import verify as tpu_verify

    def fake_verify_batch(pubs, msgs, sigs, shapes=None):
        return np.array(
            [Ed25519PubKey(p).verify_signature(m, s)
             for p, m, s in zip(pubs, msgs, sigs)], bool)

    monkeypatch.setattr(tpu_verify, "verify_batch", fake_verify_batch)

    async def go():
        pool, app = make_pool(admission_batch=16, admission_flush_ms=25.0)
        pool.admission.collector.device_threshold = 2
        met = admission_metrics()
        lanes_before = met.batch_lanes._series.get((), None)
        lanes_count0 = sum(lanes_before.counts) if lanes_before else 0
        lanes_sum0 = lanes_before.sum if lanes_before else 0.0
        dev_before = met.launches.value(backend="device")

        garbage = [garbage_tx(b"g-%d" % i) for i in range(30)]
        good = [signed_tx(b"k%d=v%d" % (i, i)) for i in range(6)]
        interleaved = []
        for i, tx in enumerate(garbage):
            interleaved.append(tx)
            if i % 5 == 0:
                interleaved.append(good[i // 5])
        results = await asyncio.gather(
            *(pool.check_tx(tx) for tx in interleaved))

        good_res = [r for tx, r in zip(interleaved, results)
                    if tx in good]
        bad_res = [r for tx, r in zip(interleaved, results)
                   if tx not in good]
        assert all(r.code == abci.CODE_TYPE_OK for r in good_res)
        assert all(r.code == CODE_ADMISSION_REJECT for r in bad_res)
        # ZERO CheckTx for shed txs: the app saw exactly the valid set
        assert app.check_calls == len(good)
        assert sorted(app.checked) == sorted(good)
        assert pool.size() == len(good)
        assert pool.admission.sheds["bad_signature"] == len(garbage)
        # multi-lane batches actually formed (sum > count ⇒ at least
        # one flush carried >1 txs) and the device backend launched
        s = met.batch_lanes._series[()]
        lanes_count = sum(s.counts) - lanes_count0
        lanes_sum = s.sum - lanes_sum0
        assert lanes_count >= 1 and lanes_sum > lanes_count, (
            f"no multi-lane batch: {lanes_count} flushes, "
            f"{lanes_sum} lanes")
        assert met.launches.value(backend="device") > dev_before
        # backlog drained and stayed within its bound
        assert pool.admission.collector.depth() == 0
        assert pool.admission.sheds["queue_full"] == 0
        pool.close()

    run(go())


# --- collector edge cases ------------------------------------------------


def _env(i: int = 0) -> tx_envelope.TxEnvelope:
    return tx_envelope.parse(signed_tx(b"edge-%d" % i))


def test_collector_deadline_flush_single_tx():
    """One lone tx must flush on the deadline, not wait for a batch."""
    async def go():
        c = AdmissionCollector(batch_max=100, flush_ms=30.0,
                               queue_max=64)
        t0 = time.monotonic()
        ok = await asyncio.wait_for(c.verify(_env()), timeout=5.0)
        dt = time.monotonic() - t0
        assert ok is True
        assert dt < 4.0  # deadline flush, not starvation
        c.close()

    run(go())


def test_collector_size_flush_races_deadline():
    """A filling batch must flush on size immediately — not park until
    a (here: absurdly long) deadline."""
    async def go():
        c = AdmissionCollector(batch_max=3, flush_ms=30_000.0,
                               queue_max=64)
        t0 = time.monotonic()
        oks = await asyncio.wait_for(
            asyncio.gather(*(c.verify(_env(i)) for i in range(3))),
            timeout=10.0)
        assert all(oks)
        assert time.monotonic() - t0 < 8.0
        c.close()

    run(go())


def test_collector_shed_newest_on_full_queue():
    """depth = pending + in-verify; at the bound the NEWEST arrival is
    shed with AdmissionQueueFullError while parked txs keep their
    place."""
    async def go():
        c = AdmissionCollector(batch_max=2, flush_ms=1.0, queue_max=4)
        gate = threading.Event()
        real = c._verify_batch

        def stalled(envs):
            gate.wait(timeout=10.0)
            return real(envs)

        c._verify_batch = stalled
        shed_before = c.queue_max and admission_metrics().sheds.value(
            reason="queue_full")
        tasks = [asyncio.ensure_future(c.verify(_env(i)))
                 for i in range(2)]
        for _ in range(200):  # wait for the flusher to take the batch
            await asyncio.sleep(0.005)
            if c._in_flight == 2:
                break
        assert c._in_flight == 2
        tasks += [asyncio.ensure_future(c.verify(_env(i)))
                  for i in range(2, 4)]
        await asyncio.sleep(0)
        assert c.depth() == 4  # 2 verifying + 2 parked: at the bound
        with pytest.raises(AdmissionQueueFullError):
            await c.verify(_env(4))
        assert admission_metrics().sheds.value(reason="queue_full") \
            == shed_before + 1
        gate.set()
        assert all(await asyncio.wait_for(asyncio.gather(*tasks),
                                          timeout=20.0))
        c.close()

    run(go())


def test_collector_host_fallback_when_breaker_open(monkeypatch):
    """An open ed25519 breaker must route admission batches to the
    host oracle — valid txs still admit, and the device is never
    launched (a production batch must not probe an open breaker)."""
    from tendermint_tpu.crypto.tpu import verify as tpu_verify

    def must_not_launch(*a, **kw):
        raise AssertionError("device launched through an open breaker")

    monkeypatch.setattr(tpu_verify, "verify_batch", must_not_launch)
    cbatch.breaker("ed25519").record_failure()  # breaker now open
    try:
        async def go():
            met = admission_metrics()
            host_before = met.launches.value(backend="host")
            c = AdmissionCollector(batch_max=4, flush_ms=5.0,
                                   queue_max=64, device_threshold=1)
            oks = await asyncio.wait_for(
                asyncio.gather(c.verify(_env(0)), c.verify(_env(1))),
                timeout=10.0)
            assert all(oks)
            assert met.launches.value(backend="host") > host_before
            c.close()

        run(go())
    finally:
        cbatch.reset_breakers()


def test_collector_sentinel_mismatch_host_recheck(monkeypatch):
    """A device batch whose known-answer sentinel lane reads invalid
    (the NaN-ing kernel shape) is re-verified on host — valid txs are
    admitted, not mass-rejected on a suspect verdict — and the
    breaker opens so the next batch skips the dead device."""
    from tendermint_tpu.crypto.tpu import verify as tpu_verify

    monkeypatch.setattr(tpu_verify, "verify_batch",
                        lambda pubs, msgs, sigs, shapes=None: np.zeros(len(pubs),
                                                          bool))
    cbatch.reset_breakers()

    async def go():
        met = admission_metrics()
        recheck_before = met.launches.value(backend="host_recheck")
        c = AdmissionCollector(batch_max=3, flush_ms=30_000.0,
                               queue_max=64, device_threshold=1)
        bad = tx_envelope.parse(garbage_tx(b"nan-bad"))
        oks = await asyncio.wait_for(
            asyncio.gather(c.verify(_env(0)), c.verify(_env(1)),
                           c.verify(bad)),
            timeout=20.0)
        assert oks == [True, True, False]
        assert met.launches.value(backend="host_recheck") \
            == recheck_before + 1
        # a wrong-verdict device is a failed device: breaker opened
        assert not cbatch.device_available("ed25519")
        c.close()

    try:
        run(go())
    finally:
        cbatch.reset_breakers()


def test_collector_all_garbage_batch_trusted_when_sentinel_verifies(
        monkeypatch):
    """An honest all-garbage device batch (every real lane invalid,
    sentinel lane valid) is TRUSTED: the flood dies at the device with
    no per-signature host re-check and the breaker stays closed."""
    from tendermint_tpu.crypto.tpu import verify as tpu_verify

    def fake_device(pubs, msgs, sigs, shapes=None):
        out = np.zeros(len(pubs), bool)
        out[-1] = True  # the sentinel lane rides last and verifies
        return out

    monkeypatch.setattr(tpu_verify, "verify_batch", fake_device)
    cbatch.reset_breakers()

    async def go():
        met = admission_metrics()
        recheck_before = met.launches.value(backend="host_recheck")
        c = AdmissionCollector(batch_max=3, flush_ms=30_000.0,
                               queue_max=64, device_threshold=1)
        oks = await asyncio.wait_for(
            asyncio.gather(*(c.verify(tx_envelope.parse(
                garbage_tx(b"junk-%d" % i))) for i in range(3))),
            timeout=20.0)
        assert oks == [False, False, False]
        assert met.launches.value(backend="host_recheck") \
            == recheck_before  # no host re-verify
        assert cbatch.device_available("ed25519")
        c.close()

    run(go())


# --- failpoint shapes ----------------------------------------------------


def test_admission_verify_failpoint_error_degrades_to_host():
    """`mempool.admission.verify` armed with `error` models a failed
    verify launch: the batch must degrade to the host oracle and valid
    txs still admit — never a mass reject, never an exception up the
    check_tx path."""
    fp.reset()
    fp.arm("mempool.admission.verify", "error")
    try:
        async def go():
            pool, app = make_pool(admission_flush_ms=5.0)
            res = await asyncio.wait_for(pool.check_tx(signed_tx(b"e1")),
                                         timeout=10.0)
            assert res.code == abci.CODE_TYPE_OK
            assert app.check_calls == 1
            # the garbage tx is still correctly rejected on host
            res = await asyncio.wait_for(pool.check_tx(garbage_tx(b"e2")),
                                         timeout=10.0)
            assert res.code == CODE_ADMISSION_REJECT
            pool.close()

        run(go())
        assert fp.state()["mempool.admission.verify"]["fires"] >= 2
    finally:
        fp.reset()


def test_admission_verify_failpoint_delay_backs_up_bounded_queue():
    """`delay` stalls the verify launch (in the executor — the loop
    keeps running): the pre-verify backlog hits its bound and sheds
    newest with 429-shaped errors instead of growing unboundedly."""
    fp.reset()
    fp.arm("mempool.admission.verify", "delay", delay_ms=300.0)
    try:
        async def go():
            pool, _ = make_pool(admission_batch=2,
                                admission_flush_ms=1.0,
                                admission_queue=3)
            txs = [signed_tx(b"d-%d" % i) for i in range(8)]
            results = await asyncio.wait_for(
                asyncio.gather(*(pool.check_tx(t) for t in txs),
                               return_exceptions=True),
                timeout=30.0)
            shed = [r for r in results
                    if isinstance(r, AdmissionQueueFullError)]
            okd = [r for r in results
                   if getattr(r, "code", -1) == abci.CODE_TYPE_OK]
            assert shed, "full pre-verify queue never shed"
            assert okd, "stalled verify starved every admit"
            assert pool.admission.sheds["queue_full"] == len(shed)
            # admission_error surfaces saturation to the RPC preflight
            pool.admission.collector._in_flight = \
                pool.admission.collector.queue_max
            assert isinstance(pool.admission_error(1),
                              AdmissionQueueFullError)
            pool.admission.collector._in_flight = 0
            pool.close()

        run(go())
    finally:
        fp.reset()


# --- TxCache poisoning pin ----------------------------------------------


def test_bad_signature_shed_never_blocks_valid_envelope_same_payload():
    """The cache keys on the FULL envelope bytes: a tx shed for a bad
    signature must not leave an entry that blocks a later, correctly
    signed envelope carrying the SAME payload — under either cache
    policy."""
    async def go():
        for keep in (False, True):
            pool, app = make_pool(keep_invalid_txs_in_cache=keep)
            payload = b"poison-%d" % keep
            res = await pool.check_tx(garbage_tx(payload))
            assert res.code == CODE_ADMISSION_REJECT
            assert app.check_calls == 0
            res = await pool.check_tx(signed_tx(payload))
            assert res.code == abci.CODE_TYPE_OK, (
                f"valid envelope blocked (keep_invalid={keep})")
            assert app.check_calls == 1
            assert pool.size() == 1
            pool.close()

    run(go())


def test_queue_full_shed_never_poisons_cache():
    """A queue_full shed is transient backpressure, not a verdict: the
    IDENTICAL envelope must be admittable on retry."""
    async def go():
        pool, app = make_pool()
        tx = signed_tx(b"retry-me")
        # fake saturation for one call
        sat = pool.admission.collector
        orig_max = sat.queue_max
        sat._in_flight = orig_max
        with pytest.raises(AdmissionQueueFullError):
            await pool.check_tx(tx)
        sat._in_flight = 0
        res = await pool.check_tx(tx)  # identical bytes
        assert res.code == abci.CODE_TYPE_OK and pool.size() == 1
        pool.close()

    run(go())


def test_unsigned_txs_not_shed_by_full_preverify_queue():
    """Permissive mode: unsigned txs never enter the pre-verify
    queue, so a garbage-envelope flood pinning that backlog full must
    not 429 them — only ENVELOPED arrivals are queue_full-shed (at
    the check_tx preflight and the RPC broadcast_tx_async preflight
    alike, which share admission_error)."""
    async def go():
        pool, app = make_pool()
        sat = pool.admission.collector
        sat._in_flight = sat.queue_max  # backlog pinned at its bound
        with pytest.raises(AdmissionQueueFullError):
            await pool.check_tx(signed_tx(b"enveloped-shed"))
        # the preflight agrees per tx shape: enveloped sheds, raw not
        assert isinstance(pool.admission_error(9, signed_tx(b"x")),
                          AdmissionQueueFullError)
        assert pool.admission_error(9, b"raw-tx-ok") is None
        res = await pool.check_tx(b"raw-unsigned-still-admits")
        assert res.code == abci.CODE_TYPE_OK
        assert app.check_calls == 1 and pool.size() == 1
        sat._in_flight = 0
        pool.close()

    run(go())


# --- WAL replay through admission ---------------------------------------


def test_wal_replay_routes_through_admission(tmp_path):
    """A restart must not re-admit WAL txs that would now fail
    pre-verification: pool1 (admission off) accepts a garbage-signed
    envelope; pool2 on the same WAL (admission on) re-admits only the
    validly signed tx and compacts the reject out of the WAL."""
    async def go():
        wal = str(tmp_path / "mwal")
        good, bad = signed_tx(b"keep"), garbage_tx(b"drop")
        app1 = CountingApp()
        pool1 = CListMempool(
            MempoolConfig(wal_dir=wal, admission="off"),
            LocalClient(app1))
        assert pool1.admission is None
        assert (await pool1.check_tx(good)).code == abci.CODE_TYPE_OK
        assert (await pool1.check_tx(bad)).code == abci.CODE_TYPE_OK
        assert pool1.size() == 2  # no plane: garbage got through
        pool1.close()

        app2 = CountingApp()
        pool2 = CListMempool(
            MempoolConfig(wal_dir=wal, admission="permissive",
                          admission_flush_ms=5.0),
            LocalClient(app2))
        report = await pool2.refill_from_wal()
        assert report == {"pending": 2, "readmitted": 1, "rejected": 1}
        assert pool2.size() == 1
        assert [m.tx for m in pool2.txs] == [good]
        # the app never paid for the garbage tx on refill either
        assert app2.checked == [good]
        # compacted: the reject cannot resurface on the NEXT restart
        assert pool2.wal_pending_txs() == [good]
        pool2.close()

    run(go())


# --- /status + admission_error surface ----------------------------------


def test_status_check_shape_and_degradation():
    async def go():
        pool, _ = make_pool()
        await pool.check_tx(signed_tx(b"st-1"))
        await pool.check_tx(b"st-plain")
        try:
            await pool.check_tx(garbage_tx(b"st-2"))
        except Exception:
            pass
        st = pool.admission.status_check()
        assert st["status"] == "ok" and st["mode"] == "permissive"
        assert st["admitted"] == {"signed": 1, "unsigned": 1}
        assert st["shed"].get("bad_signature") == 1
        assert st["queue_capacity"] == pool.config.admission_queue
        # saturated backlog degrades the check
        pool.admission.collector._in_flight = \
            pool.admission.collector.queue_max
        st = pool.admission.status_check()
        assert st["status"] == "degraded"
        pool.admission.collector._in_flight = 0
        pool.close()

    run(go())


def test_config_validation():
    MempoolConfig(admission="strict").validate_basic()
    with pytest.raises(ValueError):
        MempoolConfig(admission="banana").validate_basic()
    with pytest.raises(ValueError):
        MempoolConfig(admission_batch=0).validate_basic()
    with pytest.raises(ValueError):
        MempoolConfig(admission_flush_ms=-1).validate_basic()


def test_manifest_overload_admission_knobs():
    from tendermint_tpu.e2e.manifest import Perturbation

    p = Perturbation(node=0, op="overload", at_height=2,
                     tx_signed=0.1, tx_garbage=0.3)
    p.validate(4)
    with pytest.raises(ValueError):
        Perturbation(node=0, op="overload", at_height=2,
                     tx_signed=0.7, tx_garbage=0.7).validate(4)


def test_tx_flood_mix_is_deterministic_and_shaped():
    from tendermint_tpu.e2e.runner import tx_flood

    async def go():
        seen = []

        async def submit(tx):
            seen.append(tx)

        await tx_flood(submit, rate=400.0, duration=0.3,
                       signed_frac=0.1, garbage_frac=0.3)
        assert len(seen) > 20
        enveloped = [t for t in seen if tx_envelope.is_enveloped(t)]
        raw = [t for t in seen if not tx_envelope.is_enveloped(t)]
        assert enveloped and raw
        bad = good = 0
        for t in enveloped:
            env = tx_envelope.parse(t)
            if Ed25519PubKey(env.pub_key).verify_signature(
                    tx_envelope.sign_bytes(env.payload), env.signature):
                good += 1
            else:
                bad += 1
        assert bad > good > 0  # 30% garbage vs 10% signed

    run(go())


# --- subprocess e2e: overload + admission perturbation ------------------


@pytest.mark.slow
def test_overload_admission_perturbation(tmp_path):
    """ISSUE 6 acceptance, subprocess edition: a live net under a
    garbage-envelope flood with the admission verify stalled keeps
    monotone heights, the `admission` shed counters move, and the
    pre-verify queue stays within its bound."""
    from tendermint_tpu.e2e import Manifest, Runner

    m = Manifest.from_dict({
        "chain_id": "admission-chain",
        "nodes": 4,
        "wait_height": 7,
        "load_tx_rate": 2.0,
        "timeout_commit_ms": 150,
        "perturbations": [
            {"node": 1, "op": "overload", "at_height": 3,
             "duration": 6.0, "failpoint": "mempool.admission.verify",
             "action": "delay", "delay_ms": 10, "tx_rate": 100,
             "tx_garbage": 0.4, "tx_signed": 0.1},
        ],
    })
    logs = []
    runner = Runner(m, str(tmp_path / "net"), base_port=28900,
                    log=lambda s: logs.append(s))
    report = asyncio.run(asyncio.wait_for(runner.run(), timeout=3000))
    assert report["ok"] and report["nodes"] == 4
    assert len(runner.overload_reports) == 1
    orep = runner.overload_reports[0]
    hs = [h for h in orep["heights"] if h]
    assert hs and all(b >= a for a, b in zip(hs, hs[1:]))
    assert hs[-1] > hs[0], f"no height progress under flood: {hs}"
    # the garbage died at admission (runner also asserts this inline)
    assert orep["admission_shed_delta"] > 0, orep
    assert orep["bounded"], orep
    assert orep["cleared"], orep
