"""Named failpoint registry + device circuit breaker units.

Covers the chaos plumbing itself (libs/failpoints.py): actions,
triggers, env/config/endpoint control surfaces, the legacy
FAIL_TEST_INDEX shim's parse-once hardening — and the crypto/batch.py
circuit-breaker state machine (open -> half-open probe -> close,
per-backend independence, exponential cooldown, production batches
never touching an open breaker). The subsystem-by-subsystem injection
sweep lives in tests/test_failpoint_sweep.py.
"""

import asyncio
import json
import time

import pytest

from tendermint_tpu.libs import failpoints as fp
from tendermint_tpu.libs.failpoints import FailpointError


@pytest.fixture(autouse=True)
def _clean_registry():
    fp.reset()
    yield
    fp.reset()


# ---------------------------------------------------------------- registry

def test_unarmed_hit_is_noop_and_returns_payload():
    assert fp.hit("wal.fsync") is None
    assert fp.hit("wal.torn_write", payload=b"abc") == b"abc"


def test_error_action_and_counters():
    fp.arm("wal.fsync", "error")
    with pytest.raises(FailpointError):
        fp.hit("wal.fsync")
    st = fp.state()["wal.fsync"]
    assert st["hits"] == 1 and st["fires"] == 1
    assert st["armed"] == {"action": "error"}


def test_nth_trigger_fires_exactly_once():
    fp.arm("db.set", "error", nth=3)
    fp.hit("db.set")
    fp.hit("db.set")
    with pytest.raises(FailpointError):
        fp.hit("db.set")
    fp.hit("db.set")  # past the nth: never again
    st = fp.state()["db.set"]
    assert st["hits"] == 4 and st["fires"] == 1


def test_every_trigger():
    fp.arm("db.set", "error", every=2)
    fired = 0
    for _ in range(6):
        try:
            fp.hit("db.set")
        except FailpointError:
            fired += 1
    assert fired == 3


def test_count_auto_disarms():
    fp.arm("db.set", "error", count=2)
    for _ in range(2):
        with pytest.raises(FailpointError):
            fp.hit("db.set")
    fp.hit("db.set")  # disarmed
    assert fp.state()["db.set"]["armed"] is None


def test_corrupt_transforms_payload_and_degrades_without_one():
    fp.arm("wal.torn_write", "corrupt")
    out = fp.hit("wal.torn_write", payload=b"x" * 64)
    assert out != b"x" * 64 and len(out) == 63
    fp.arm("wal.fsync", "corrupt")
    with pytest.raises(FailpointError):  # no payload at this site
        fp.hit("wal.fsync")


def test_delay_action_sleeps():
    fp.arm("wal.fsync", "delay", delay_ms=30)
    t0 = time.monotonic()
    fp.hit("wal.fsync")
    assert time.monotonic() - t0 >= 0.025


def test_prob_zero_never_fires():
    fp.arm("db.set", "error", prob=0.0)
    for _ in range(20):
        fp.hit("db.set")
    assert fp.state()["db.set"]["fires"] == 0


def test_arm_rejects_unknown_name_and_action():
    with pytest.raises(ValueError):
        fp.arm("no.such.point", "error")
    with pytest.raises(ValueError):
        fp.arm("wal.fsync", "explode")
    with pytest.raises(ValueError):
        fp.arm("wal.fsync", "error", nth=0)


# -------------------------------------------------------- control surfaces

def test_env_spec_parsed_once_and_lenient(monkeypatch):
    monkeypatch.setenv(
        fp.ENV_VAR,
        "wal.fsync=error;nth=1, bogus.point=error, db.set=oops, "
        "db.set=delay:15")
    fp.reset()  # forces re-read on next hit
    with pytest.raises(FailpointError):
        fp.hit("wal.fsync")
    # malformed entries were skipped, valid later ones still armed
    t0 = time.monotonic()
    fp.hit("db.set")
    assert time.monotonic() - t0 >= 0.01
    assert "bogus.point" not in fp.any_armed()


def test_legacy_fail_test_index_counts_named_sites(monkeypatch):
    exits = []
    monkeypatch.setattr(fp.os, "_exit", lambda code: exits.append(code))
    monkeypatch.setenv(fp.LEGACY_ENV_VAR, "2")
    fp.reset()
    fp.hit("consensus.commit.block_saved")   # ordinal 0
    fp.hit("consensus.commit.wal_delimited")  # ordinal 1
    assert not exits
    fp.hit("state.apply.block_executed")     # ordinal 2 -> crash
    assert exits == [1]
    # non-legacy points never advance the ordinal
    fp.hit("wal.fsync")


def test_legacy_fail_test_index_malformed_is_ignored(monkeypatch):
    """The satellite: int(env) used to run on EVERY fail() call and a
    malformed value raised from inside consensus. Now it parses once
    and bad values are logged + ignored."""
    monkeypatch.setenv(fp.LEGACY_ENV_VAR, "not-a-number")
    fp.reset()
    fp.hit("consensus.commit.block_saved")  # must not raise
    from tendermint_tpu.libs.fail import fail

    fail()  # legacy entry point must not raise either


def test_legacy_shim_fail_still_crashes_at_index(monkeypatch):
    exits = []
    monkeypatch.setattr(fp.os, "_exit", lambda code: exits.append(code))
    monkeypatch.setenv(fp.LEGACY_ENV_VAR, "0")
    fp.reset()
    from tendermint_tpu.libs.fail import fail

    fail()
    assert exits == [1]


# ------------------------------------------------------------ debug server

def test_debug_failpoint_endpoint():
    """POST arms / disarms through the DebugServer; GET reports the
    catalog with counters; bad requests come back as {"error"}."""
    from tendermint_tpu.libs.debugsrv import DebugServer

    async def go():
        srv = DebugServer()
        port = await srv.start()

        async def req(method, path, payload=None):
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", port)
            body = json.dumps(payload).encode() if payload else b""
            writer.write(
                f"{method} {path} HTTP/1.0\r\n"
                f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
            await writer.drain()
            raw = await asyncio.wait_for(reader.read(), timeout=10)
            writer.close()
            return json.loads(raw.partition(b"\r\n\r\n")[2])

        try:
            res = await req("POST", "/debug/failpoint",
                            {"name": "wal.fsync", "action": "error",
                             "nth": 2})
            assert res.get("ok") and "wal.fsync" in res["armed"]
            fp.hit("wal.fsync")
            with pytest.raises(FailpointError):
                fp.hit("wal.fsync")
            got = await req("GET", "/debug/failpoint")
            assert got["wal.fsync"]["hits"] == 2
            assert got["wal.fsync"]["fires"] == 1
            assert got["wal.fsync"]["armed"]["nth"] == 2
            # armed chaos shows up in /status as a degraded check
            st = await req("GET", "/status")
            assert st["checks"]["failpoints"]["status"] == "degraded"
            assert "wal.fsync" in st["checks"]["failpoints"]["armed"]
            res = await req("POST", "/debug/failpoint",
                            {"name": "wal.fsync", "action": "off"})
            assert res.get("ok") and res["armed"] == []
            st = await req("GET", "/status")
            assert "failpoints" not in st["checks"]
            res = await req("POST", "/debug/failpoint",
                            {"name": "bogus", "action": "error"})
            assert "error" in res
        finally:
            srv.close()

    asyncio.run(go())


# --------------------------------------------------------- circuit breaker

def test_breaker_state_machine_probe_and_exponential_cooldown():
    from tendermint_tpu.crypto import batch as B

    results = [False, False, True]
    probes = []

    def probe():
        r = results.pop(0)
        probes.append(r)
        return r

    br = B.CircuitBreaker("unit", probe)
    orig = B.BREAKER_BASE_COOLDOWN_S
    B.BREAKER_BASE_COOLDOWN_S = 0.04
    try:
        assert br.acquire() and br.state == B.CLOSED
        br.record_failure()
        assert br.state == B.OPEN
        cd1 = br.cooldown_remaining()
        assert not br.acquire()           # still cooling: host path
        assert probes == []               # no probe before expiry
        time.sleep(cd1 + 0.02)
        assert not br.acquire()           # probe #1 fails -> reopen
        cd2 = br.cooldown_remaining()
        # exponential: second cooldown ~2x the first (jitter ±20%)
        assert cd2 > cd1 * 1.3
        time.sleep(cd2 + 0.02)
        assert not br.acquire()           # probe #2 fails -> reopen
        time.sleep(br.cooldown_remaining() + 0.02)
        assert br.acquire()               # probe #3 ok -> closed
        assert br.state == B.CLOSED and br.consecutive_failures == 0
        assert probes == [False, False, True]
    finally:
        B.BREAKER_BASE_COOLDOWN_S = orig


def test_breaker_per_backend_independence():
    from tendermint_tpu.crypto import batch as B

    B.reset_breakers()
    try:
        B.mark_device_failed("sr25519")
        assert not B.device_available("sr25519")
        assert B.device_available("ed25519")
        assert not B.device_available()  # any-open legacy reading
        assert B.breaker_states() == {"ed25519": "closed",
                                      "sr25519": "open"}
    finally:
        B.reset_breakers()


def test_production_batch_never_launches_while_open(monkeypatch):
    """The acceptance bar: with a dead device, post-breaker cost is
    one PROBE-sized batch per cooldown window — a production commit
    batch never reaches an open breaker."""
    from tendermint_tpu.crypto import batch as B
    from tendermint_tpu.crypto.ed25519 import Ed25519PrivKey
    from tendermint_tpu.crypto.tpu import verify as tv

    launches = []

    def boom(pubs, msgs, sigs, shapes=None):
        launches.append(len(pubs))
        raise RuntimeError("dead device")

    monkeypatch.setattr(tv, "verify_batch", boom)
    monkeypatch.setattr(B, "BREAKER_BASE_COOLDOWN_S", 0.6)
    B.reset_breakers()
    try:
        sk = Ed25519PrivKey.generate()
        triples = [(sk.pub_key(), b"m%d" % i, sk.sign(b"m%d" % i))
                   for i in range(50)]  # a "production" batch

        def production_verify():
            bv = B.BatchVerifier(use_device=True)
            for pk, m, s in triples:
                bv.add(pk, m, s)
            ok, v = bv.verify()
            assert ok and v.all()  # host verdicts stay correct

        production_verify()                 # opens the breaker
        assert launches == [50]
        production_verify()                 # open: no launch at all
        assert launches == [50]
        # past the cooldown (0.6s ± 20% jitter): the next verify runs
        # the half-open probe — and ONLY the probe reaches the device
        time.sleep(B.breaker("ed25519").cooldown_remaining() + 0.05)
        production_verify()
        assert len(launches) == 2
        assert launches[1] == B.PROBE_LANES  # probe-sized, not 50
        assert not B.device_available("ed25519")  # probe failed
    finally:
        B.reset_breakers()


def test_breaker_closes_on_successful_probe_and_readmits(monkeypatch):
    from tendermint_tpu.crypto import batch as B
    from tendermint_tpu.crypto.ed25519 import Ed25519PrivKey
    from tendermint_tpu.crypto.tpu import verify as tv
    import numpy as np

    alive = {"up": False}
    launches = []

    def flaky(pubs, msgs, sigs, shapes=None):
        launches.append(len(pubs))
        if not alive["up"]:
            raise RuntimeError("dead device")
        return np.ones(len(pubs), bool)

    monkeypatch.setattr(tv, "verify_batch", flaky)
    monkeypatch.setattr(B, "BREAKER_BASE_COOLDOWN_S", 0.05)
    B.reset_breakers()
    try:
        sk = Ed25519PrivKey.generate()
        bv = B.BatchVerifier(use_device=True)
        bv.add(sk.pub_key(), b"m", sk.sign(b"m"))
        assert bv.verify()[0]               # opens breaker
        alive["up"] = True                  # device "recovers"
        time.sleep(0.12)
        bv2 = B.BatchVerifier(use_device=True)
        bv2.add(sk.pub_key(), b"m", sk.sign(b"m"))
        assert bv2.verify()[0]
        # probe ran AND the production batch was admitted afterwards
        assert launches[-2] == B.PROBE_LANES and launches[-1] == 1
        assert B.device_available("ed25519")
    finally:
        B.reset_breakers()


def test_device_verify_failpoint_opens_breaker():
    """Arming device.verify=error makes every device launch AND every
    half-open probe fail — the breaker must open and stay open, with
    all verification degraded to host, verdicts intact."""
    from tendermint_tpu.crypto import batch as B
    from tendermint_tpu.crypto.ed25519 import Ed25519PrivKey

    fp.arm("device.verify", "error")
    B.reset_breakers()
    try:
        sk = Ed25519PrivKey.generate()
        bv = B.BatchVerifier(use_device=True)
        bv.add(sk.pub_key(), b"m", sk.sign(b"m"))
        ok, v = bv.verify()
        assert ok and list(v) == [True]
        assert not B.device_available("ed25519")
    finally:
        B.reset_breakers()


# ------------------------------------------------------------------- lint

def test_check_failpoints_lint():
    import os
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    import check_failpoints

    problems = check_failpoints.collect_problems()
    assert not problems, "\n".join(problems)
