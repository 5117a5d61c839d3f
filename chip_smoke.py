#!/usr/bin/env python3
"""chip_smoke.py — the node and the 10,000-validator commit check, end
to end, on the chip this process can open.

    python chip_smoke.py               one chip: phases A, B, C
    python chip_smoke.py --four-chips  four chips of one host: phase D only
    python chip_smoke.py --rehearse    the same control flow at tiny sizes
                                       on whatever backend JAX has (CPU
                                       here); never prints the result line

A. A node serves: `python -m tendermint_tpu.cmd init` + `start` as a
   child ([crypto] backend = "tpu", debug server on, built-in kvstore,
   real TCP RPC); signed enveloped txs through broadcast_tx_sync, a few
   with bad signatures; broadcast_tx_commit + abci_query read-back;
   /debug/launches, /metrics and /status read over HTTP. The parent
   does not touch JAX until that child has exited: one process per chip.
B. A 10,000-validator all-ed25519 ValidatorSet, a real Commit over
   canonical precommit sign bytes, through ValidatorSet.verify_commit
   and verify_commit_light; the same commit with two signatures
   corrupted and one vote absent; BatchVerifier(use_device=True) lane
   by lane against ed25519_ref.verify.
C. The general kernel (mixed unknown keys, a ZIP-215-only signature),
   sr25519, and two launches of the donated-buffer ResidentArena, each
   against its host reference.
D. Phase B's commit over the ('dp',) mesh with replicated and then with
   key-range-sharded tables, lane by lane against the single-device
   result and the host reference, with per-device resident bytes.

One JSON object per phase on earlier lines (facts about this run, not
metrics). Any phase that fails ends the script non-zero with no result
line; after every phase the launch ledger, tpu_host_fallbacks_total and
the breakers are read, and a launch that landed off the chip, raised or
degraded to the host is a failure. The last line of a passing run is
exactly {"ok": true, "device": {"platform", "kind", "count"}}.
"""

from __future__ import annotations

import argparse
import asyncio
import base64
import concurrent.futures
import hashlib
import json
import multiprocessing
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

CHAIN_ID = "chip-smoke"
HEIGHT = 123456
BASE_TS = 1_753_928_000_000_000_000

# validators: the reference's MaxVotesCount (types/vote_set.go). The
# rehearsal cut keeps every path (>= 128 lanes for the comb tables,
# >= 40 for device admission batches) at sizes a CPU finishes quickly.
FULL = dict(txs=512, bad_txs=8, commit_txs=4, validators=10_000,
            general_lanes=1024, sr_lanes=1024, arena_lanes=None,
            shard_crossover=1024)
REHEARSE = dict(txs=96, bad_txs=3, commit_txs=2, validators=256,
                general_lanes=128, sr_lanes=128, arena_lanes=384,
                shard_crossover=64)


WAVE = 100  # txs in flight at once in phase A


class SmokeFailure(Exception):
    pass


def emit(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, **facts}), flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ------------------------------------------------ host references (pool)


def _ref_chunk(kind: str, triples: list) -> list[bool]:
    """Pool worker: the plain host oracle, one lane at a time. Imports
    only the pure-Python references — a worker never touches JAX."""
    if kind == "sr25519":
        from tendermint_tpu.crypto import sr25519_ref as ref
    else:
        from tendermint_tpu.crypto import ed25519_ref as ref
    return [len(p) == 32 and len(s) == 64 and bool(ref.verify(p, m, s))
            for p, m, s in triples]


class RefPool:
    """ed25519_ref / sr25519_ref verdicts over a spawn-context process
    pool (~4 ms a lane in pure Python; 10,000 lanes on one core would
    be most of a minute)."""

    def __init__(self):
        self._pool = concurrent.futures.ProcessPoolExecutor(
            max_workers=max(2, min(12, (os.cpu_count() or 2) - 1)),
            mp_context=multiprocessing.get_context("spawn"))

    def submit(self, fn, *args):
        return self._pool.submit(fn, *args)

    def verdicts(self, kind: str, triples: list):
        import numpy as np

        step = 256
        futs = [self.submit(_ref_chunk, kind, triples[i:i + step])
                for i in range(0, len(triples), step)]
        return np.asarray([v for f in futs for v in f.result()], bool)

    def close(self) -> None:
        self._pool.shutdown(wait=True, cancel_futures=True)


# ------------------------------------------------------- phase A: a node


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http_get(port: int, path: str) -> bytes:
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=30) as r:
        return r.read()


def _tx_key(i: int, tag: bytes) -> bytes:
    """Signed enveloped tx i, its key salted until the envelope holds
    no '=' byte: the kvstore splits a tx on its first '=', so such a tx is
    stored under itself and reads back as itself."""
    from tendermint_tpu.crypto.ed25519 import Ed25519PrivKey
    from tendermint_tpu.types import tx_envelope

    payload = b"smoke-%s-%06d" % (tag, i)
    for salt in range(1000):
        priv = Ed25519PrivKey(hashlib.sha256(
            b"chip-smoke tx %s %d %d" % (tag, i, salt)).digest())
        tx = tx_envelope.sign_tx(priv, payload)
        if b"=" not in tx:
            return tx
    raise SmokeFailure("no '='-free envelope in 1000 salts")


def _corrupt_sig(tx: bytes) -> bytes:
    """Flip one bit inside the envelope's signature field."""
    from tendermint_tpu.types import tx_envelope

    env = tx_envelope.parse(tx)
    sig = bytearray(env.signature)
    sig[7] ^= 0x01
    return tx_envelope.encode(env.pub_key, bytes(sig), env.payload)


async def _drive_node(rpc_port: int, dbg_port: int, size: dict,
                      platform: str, proc) -> dict:
    from tendermint_tpu.mempool.admission import CODE_ADMISSION_REJECT
    from tendermint_tpu.rpc.jsonrpc import CODE_BUSY, HTTPClient, RPCError

    # a first device batch waits for its kernel to compile
    cli = HTTPClient("127.0.0.1", rpc_port, timeout=900.0)

    async def height() -> int:
        st = await cli.call("status")
        return int(st["sync_info"]["latest_block_height"])

    t0 = time.monotonic()
    while True:
        check(proc.poll() is None,
              f"node exited rc={proc.returncode} before serving")
        try:
            if await height() >= 3:
                break
        except (OSError, ValueError, RPCError, asyncio.TimeoutError):
            pass
        check(time.monotonic() - t0 < 300, "node never reached height 3")
        await asyncio.sleep(0.5)
    boot_s = time.monotonic() - t0

    good = [_tx_key(i, b"sync") for i in range(size["txs"])]
    bad = [_corrupt_sig(_tx_key(i, b"bad"))
           for i in range(size["bad_txs"])]
    # bad ones spread through the flood, not bunched at its end
    flood = list(good)
    for j, tx in enumerate(bad):
        flood.insert((j + 1) * len(flood) // (len(bad) + 1), tx)
    busy_retries = 0

    async def send(tx: bytes) -> dict:
        nonlocal busy_retries
        while True:
            try:
                return await cli.call(
                    "broadcast_tx_sync",
                    tx=base64.b64encode(tx).decode())
            except RPCError as e:
                # pre-verify backlog full: transient, retry later
                if e.code != CODE_BUSY:
                    raise
                busy_retries += 1
                await asyncio.sleep(0.25)

    def node_view(when: str, fresh: bool) -> dict:
        """What the node itself says about where its launches ran
        (/debug/launches, /metrics, /status), checked: nothing off the
        chip, raised or degraded, and the device check healthy."""
        from tools.bench_util import chip_faults

        launches = json.loads(_http_get(dbg_port, "/debug/launches"))
        device = json.loads(
            _http_get(dbg_port, "/status"))["checks"]["device"]
        fallbacks = 0.0
        for line in _http_get(dbg_port, "/metrics").decode().splitlines():
            if line.startswith("tpu_host_fallbacks_total"):
                fallbacks += float(line.rsplit(" ", 1)[1])
        adm = [r for r in launches["records"]
               if r["workload"] == "admission"]
        view = {
            "admission_launches": len(adm),
            "admission_lanes": sum(r["lanes"] for r in adm),
            "admission_buckets": sorted({r["capacity"] for r in adm}),
            "admission_devices": sorted({r["device"] for r in adm}),
            "host_fallbacks": fallbacks,
            "breakers": device["breakers"],
            "effective_backend": device.get("effective_backend"),
            "last_device_launch_age_s":
                device.get("last_device_launch_age_s"),
            "device_check": device["status"],
        }
        print(f"chip_smoke: node view {when}: {json.dumps(view)}",
              file=sys.stderr, flush=True)
        faults = chip_faults(launches["records"], fallbacks,
                             device["breakers"], platform)
        check(not faults, f"node left the chip ({when}): "
              + "; ".join(faults))
        check(adm, "no admission launch in the ledger: the flood never "
                   "formed a device batch")
        # `fresh`: the flood's launches are still inside the watchdog's
        # window, so the device check must be healthy; a node that has
        # then sat quiet for a minute is rightly called idle
        check(not fresh or device["status"] == "ok",
              f"/status device check ({when}): {device}")
        return view

    # Waves of WAVE txs, each wave all in flight at once: enough
    # concurrency that the collector cuts device batches (>= 40 lanes
    # inside its 2 ms window), and with the sentinel lane every batch
    # pads to ONE kernel bucket (128) — one compile, not one per size.
    t0 = time.monotonic()
    results = {}
    for i in range(0, len(flood), WAVE):
        wave = flood[i:i + WAVE]
        results.update(zip(wave, await asyncio.gather(*map(send, wave))))
    flood_s = time.monotonic() - t0
    print(f"chip_smoke: node at height 3 after {boot_s:.1f}s; flood of "
          f"{len(flood)} txs answered in {flood_s:.1f}s "
          f"({busy_retries} busy retries)", file=sys.stderr, flush=True)
    view = node_view("after the flood", fresh=True)
    rejected = [tx for tx in bad
                if results[tx]["code"] == CODE_ADMISSION_REJECT]
    check(len(rejected) == len(bad),
          f"{len(bad) - len(rejected)} bad-signature txs were admitted")
    not_ok = [results[tx] for tx in good if results[tx]["code"] != 0]
    check(not not_ok, f"good txs refused by CheckTx: {not_ok[:3]}")

    commit_txs = [_tx_key(i, b"commit") for i in range(size["commit_txs"])]
    for tx in commit_txs:
        res = await cli.call("broadcast_tx_commit",
                             tx=base64.b64encode(tx).decode())
        check(res["check_tx"]["code"] == 0
              and res["deliver_tx"]["code"] == 0
              and int(res["height"]) > 0,
              f"broadcast_tx_commit failed: {res}")

    async def stored(tx: bytes) -> bool:
        q = await cli.call("abci_query", path="", data=tx.hex())
        return base64.b64decode(q["response"]["value"] or "") == tx

    pending = list(good + commit_txs)
    t0 = time.monotonic()
    while pending:
        found = []
        for i in range(0, len(pending), WAVE):
            found += await asyncio.gather(*map(stored, pending[i:i + WAVE]))
        pending = [tx for tx, ok in zip(pending, found) if not ok]
        if pending:
            check(time.monotonic() - t0 < 120,
                  f"{len(pending)} good txs never read back")
            await asyncio.sleep(0.5)
    readback_s = time.monotonic() - t0
    for tx in bad:
        check(not await stored(tx), "a bad-signature tx was committed")
    node_view("after the read-back", fresh=False)
    return {
        "height": await height(),
        "boot_s": round(boot_s, 3),
        "committed": len(good) + len(commit_txs),
        "rejected_bad_signature": len(rejected),
        "flood_s": round(flood_s, 3),
        "readback_s": round(readback_s, 3),
        "busy_retries": busy_retries,
        **view,
    }


def phase_a(size: dict, rehearse: bool, platform: str) -> None:
    from tendermint_tpu.config import Config

    home = tempfile.mkdtemp(prefix="chip_smoke_node_")
    proc = log = None
    try:
        cmd = [sys.executable, "-m", "tendermint_tpu.cmd"]
        env = dict(os.environ, PYTHONPATH=REPO + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        subprocess.run(cmd + ["init", "--home", home], check=True,
                       env=env, stdout=subprocess.DEVNULL, timeout=120)
        cfg_path = os.path.join(home, "config", "config.toml")
        cfg = Config.load(cfg_path)
        # default config but for the chip promise, the debug server
        # and free ports; a rehearsal has no chip to promise
        cfg.crypto.backend = "auto" if rehearse else "tpu"
        rpc_port, dbg_port = _free_port(), _free_port()
        cfg.rpc.laddr = f"tcp://127.0.0.1:{rpc_port}"
        cfg.rpc.pprof_laddr = f"127.0.0.1:{dbg_port}"
        cfg.p2p.laddr = f"tcp://127.0.0.1:{_free_port()}"
        cfg.save(cfg_path)
        log = open(os.path.join(home, "node.log"), "w+")
        t0 = time.monotonic()
        proc = subprocess.Popen(cmd + ["start", "--home", home], env=env,
                                stdout=log, stderr=subprocess.STDOUT)
        facts = asyncio.run(
            _drive_node(rpc_port, dbg_port, size, platform, proc))
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=60)
        check(rc == 0, f"node did not stop cleanly: rc={rc}")
        check("jax" not in sys.modules,
              "the parent imported jax while the node child held the chip")
        emit("A", **facts, node_s=round(time.monotonic() - t0, 3),
             stopped="clean")
    except BaseException:
        if log is not None:
            log.flush()
            log.seek(0)
            sys.stderr.write("---- node log (tail) ----\n"
                             + log.read()[-6000:] + "\n")
        raise
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait()
        if log is not None:
            log.close()
        shutil.rmtree(home, ignore_errors=True)


# --------------------------------------- the valset and its commit (B, D)


class CommitFixture:
    """An n-validator all-ed25519 ValidatorSet (keys from a fixed
    seed), a real Commit signed over canonical precommit sign bytes,
    and the same commit with signatures `bad` corrupted (and, for
    verify_commit, validator `absent`'s vote missing)."""

    def __init__(self, n: int):
        from tendermint_tpu.crypto.ed25519 import Ed25519PrivKey
        from tendermint_tpu.types.block import (
            BlockID, BlockIDFlag, Commit, CommitSig, PartSetHeader)
        from tendermint_tpu.types.validator import Validator
        from tendermint_tpu.types.validator_set import ValidatorSet

        privs = [Ed25519PrivKey(hashlib.sha256(
            b"chip-smoke validator %d" % i).digest()) for i in range(n)]
        by_addr = {p.pub_key().address(): p for p in privs}
        self.vals = ValidatorSet(
            [Validator.new(p.pub_key(), 1) for p in privs])
        self.n = n
        self.block_id = BlockID(
            hash=b"\xab" * 32,
            part_set_header=PartSetHeader(4, b"\xcd" * 32))
        order = self.vals.validators
        self.pubs = [v.pub_key.bytes() for v in order]

        def commit_of(sigs):
            return Commit(height=HEIGHT, round=0, block_id=self.block_id,
                          signatures=sigs)

        def slot(i, sig=b""):
            return CommitSig(BlockIDFlag.COMMIT, order[i].address,
                             BASE_TS + i * 1_000_003, sig)

        unsigned = commit_of([slot(i) for i in range(n)])
        self.msgs = [unsigned.vote_sign_bytes(CHAIN_ID, i)
                     for i in range(n)]
        self.sigs = [by_addr[order[i].address].sign(self.msgs[i])
                     for i in range(n)]
        self.good = commit_of([slot(i, self.sigs[i]) for i in range(n)])

        self.bad = sorted({n // 3, (2 * n) // 3 + 1})
        self.absent = n // 2
        self.bad_sigs = list(self.sigs)
        for i in self.bad:
            s = bytearray(self.sigs[i])
            s[40] ^= 0x01  # a wrong S, still < L with these seeds' luck
            self.bad_sigs[i] = bytes(s)
        # every lane present, two corrupted: the lane-by-lane commit
        self.corrupt = commit_of(
            [slot(i, self.bad_sigs[i]) for i in range(n)])
        # ... and with one vote absent: what verify_commit must reject
        self.corrupt_absent = commit_of(
            [CommitSig.absent() if i == self.absent
             else slot(i, self.bad_sigs[i]) for i in range(n)])

    def triples(self):
        return list(zip(self.pubs, self.msgs, self.bad_sigs))

    def expect_rejected(self, verify, what: str) -> None:
        """verify(corrupt_absent) must raise naming exactly self.bad."""
        from tendermint_tpu.types.validator_set import VerificationError

        try:
            verify(self.corrupt_absent)
        except VerificationError as e:
            check(str(e) == f"invalid signature(s) at index(es) {self.bad}",
                  f"{what} named the wrong lanes: {e}")
        else:
            raise SmokeFailure(f"{what} accepted a corrupted commit")


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, round(time.perf_counter() - t0, 3)


def _compile_seconds() -> float:
    """Seconds this process has spent in XLA compiles so far (JAX's own
    /jax/compilation_cache and backend-compile monitoring events)."""
    return round(_COMPILE_S[0], 3)


_COMPILE_S = [0.0]


def _watch_compiles() -> None:
    from jax import monitoring

    def on_duration(event: str, secs: float, **kw) -> None:
        if event.endswith("backend_compile_duration"):
            _COMPILE_S[0] += secs

    monitoring.register_event_duration_secs_listener(on_duration)


def _assert_on_chip(phase: str, platform: str) -> None:
    from tools.bench_util import chip_faults

    faults = chip_faults(platform=platform)
    check(not faults, f"phase {phase} left the chip: " + "; ".join(faults))


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ------------------------- phases B and C: the commit and the kernels
#
# Every kernel shape is a 40-100 s XLA compile, mostly on one core, and
# the phases compile nine of them. Their first calls therefore run as
# concurrent tasks (XLA compiles outside the GIL); the warm timings are
# taken afterwards, one at a time, with nothing else running.


def _commit_calls(fx: CommitFixture):
    def verify_commit(commit):
        fx.vals.verify_commit(CHAIN_ID, fx.block_id, HEIGHT, commit)

    def verify_light(commit):
        fx.vals.verify_commit_light(CHAIN_ID, fx.block_id, HEIGHT, commit)

    return verify_commit, verify_light


def task_commit(fx: CommitFixture) -> dict:
    """B: the entry point consensus and fast sync call. The first call
    builds the tables, compiles and launches, and ends in the host
    readback of the verdicts."""
    from tendermint_tpu.crypto.tpu import expanded as ex
    from tendermint_tpu.libs.metrics import tpu_metrics

    verify_commit, _ = _commit_calls(fx)
    _, first_s = _timed(lambda: verify_commit(fx.good))
    build_s = round(tpu_metrics().expanded_build_seconds.sum, 3)
    fx.expect_rejected(verify_commit, "verify_commit")
    exp = ex.get_expanded(fx.pubs)
    check(int(exp.tables.shape[0]) == fx.n * 69 * 9,
          f"tables hold {exp.tables.shape} rows for {fx.n} keys")
    return dict(table_bytes=int(exp.tables.nbytes), table_build_s=build_s,
                verify_commit_first_s=first_s,
                rejected_indexes=fx.bad, absent_index=fx.absent)


def task_commit_light(fx: CommitFixture) -> dict:
    """B: the light client's entry point — the cheapest 2/3 of the
    power, another lane bucket of the same kernel (it waits for
    task_commit's table build, then compiles beside it)."""
    _, verify_light = _commit_calls(fx)
    _, first_s = _timed(lambda: verify_light(fx.good))
    return dict(verify_commit_light_first_s=first_s)


def task_batch_lanes(fx: CommitFixture, pool: RefPool) -> dict:
    """B: per-lane verdicts of the general batch API over the same
    triples, against ed25519_ref lane by lane."""
    import numpy as np

    from tendermint_tpu.crypto.batch import BatchVerifier
    from tendermint_tpu.crypto.ed25519 import Ed25519PubKey

    triples = fx.triples()
    ref, ref_s = _timed(lambda: pool.verdicts("ed25519", triples))
    want = np.ones(fx.n, bool)
    want[fx.bad] = False
    check((ref == want).all(), "the host reference disagrees with how "
                               "the commit was corrupted")
    bv = BatchVerifier(use_device=True)
    for p, m, s in triples:
        bv.add(Ed25519PubKey(p), m, s)
    (all_ok, lanes), batch_s = _timed(bv.verify)
    check(not all_ok and (lanes == ref).all(),
          f"BatchVerifier lanes differ from ed25519_ref at "
          f"{np.flatnonzero(lanes != ref)[:8].tolist()}")
    return dict(batch_verifier_lanes=fx.n, batch_verifier_first_s=batch_s,
                lanes_equal_ed25519_ref=True, ref_s=ref_s)


def _general_triples(n: int):
    """Mixed unknown keys: valid lanes, each kind of invalid lane, and
    one signature only ZIP-215 accepts (non-canonical small-order R)."""
    from tendermint_tpu.crypto import ed25519_ref as ref
    from tendermint_tpu.crypto.ed25519 import Ed25519PrivKey

    seeds = [hashlib.sha256(b"chip-smoke general %d" % i).digest()
             for i in range(n)]
    pubs = [ref.public_key_from_seed(s) for s in seeds]
    msgs = [b"general kernel lane %d" % i for i in range(n)]
    sigs = [Ed25519PrivKey(s).sign(m) for s, m in zip(seeds, msgs)]
    msgs[1] += b"!"                                      # wrong message
    sigs[2] = bytes([sigs[2][0] ^ 1]) + sigs[2][1:]      # corrupt R
    sigs[3] = sigs[3][:33] + bytes([sigs[3][33] ^ 1]) + sigs[3][34:]
    pubs[4] = (2).to_bytes(32, "little")                 # off-curve A
    # ZIP-215-only: R encodes y = p + 1 (the identity, non-canonically)
    a = ref._clamp(hashlib.sha512(seeds[5]).digest())
    r_enc = (ref.P + 1).to_bytes(32, "little")
    k = int.from_bytes(hashlib.sha512(
        r_enc + pubs[5] + msgs[5]).digest(), "little") % ref.L
    sigs[5] = r_enc + ((k * a) % ref.L).to_bytes(32, "little")
    return pubs, msgs, sigs, 5


def task_general(size: dict, pool: RefPool) -> dict:
    """C: verify.verify_batch over mixed unknown keys."""
    import numpy as np

    from tendermint_tpu.crypto.tpu import verify as tv

    pubs, msgs, sigs, zip215_lane = _general_triples(size["general_lanes"])
    ref = pool.verdicts("ed25519", list(zip(pubs, msgs, sigs)))
    check(bool(ref[zip215_lane]) and not ref[1:5].any()
          and ref[6:].all(), "general-kernel fixture is not what it says")
    got, first_s = _timed(lambda: tv.verify_batch(pubs, msgs, sigs))
    check((got == ref).all(), "verify_batch lanes differ from "
          f"ed25519_ref at {np.flatnonzero(got != ref)[:8].tolist()}")
    return dict(general_lanes=len(pubs), general_first_s=first_s,
                zip215_only_accepted=True, lanes_equal_ed25519_ref=True)


def _sr_sign_chunk(chunk: list) -> list[bytes]:
    from tendermint_tpu.crypto import sr25519_ref as sr

    return [sr.sign(mini, msg) for mini, msg in chunk]


def task_sr25519(size: dict, pool: RefPool) -> dict:
    """C: sr_verify.verify_batch_sr. Keys and signatures come from the
    pure-Python reference too, so they are made in the pool."""
    import numpy as np

    from tendermint_tpu.crypto import sr25519_ref as sr
    from tendermint_tpu.crypto.tpu import sr_verify

    n = size["sr_lanes"]
    minis = [hashlib.sha256(b"chip-smoke sr %d" % i).digest()
             for i in range(n)]
    pubs = [sr.public_key_from_mini(m) for m in minis]
    msgs = [b"sr25519 lane %d" % i for i in range(n)]
    todo = list(zip(minis, msgs))
    futs = [pool.submit(_sr_sign_chunk, todo[i:i + 64])
            for i in range(0, n, 64)]
    sigs = [s for f in futs for s in f.result()]
    msgs[2] += b"!"
    sigs[3] = sigs[3][:5] + bytes([sigs[3][5] ^ 1]) + sigs[3][6:]
    ref = pool.verdicts("sr25519", list(zip(pubs, msgs, sigs)))
    check(not ref[2] and not ref[3] and ref.sum() == n - 2,
          "sr25519 fixture is not what it says")
    got, first_s = _timed(
        lambda: sr_verify.verify_batch_sr(pubs, msgs, sigs))
    check((got == ref).all(), "verify_batch_sr lanes differ from "
          f"sr25519_ref at {np.flatnonzero(got != ref)[:8].tolist()}")
    return dict(sr25519_lanes=n, sr25519_first_s=first_s,
                lanes_equal_sr25519_ref=True)


def task_arena(size: dict, fx: CommitFixture) -> dict:
    """C: the resident arena through the speculation plane that owns
    it. The commit's precommits arrive in two halves; the second
    launch splices into the buffers the first one donated."""
    import numpy as np

    from tendermint_tpu.config import SpeculationConfig
    from tendermint_tpu.consensus.speculation import SpeculationPlane
    from tendermint_tpu.types.vote import Vote, VoteType

    cfg = SpeculationConfig() if size["arena_lanes"] is None else \
        SpeculationConfig(arena_lanes=size["arena_lanes"])
    plane = SpeculationPlane(cfg, device_min=1)
    try:
        plane.begin_height(CHAIN_ID, fx.vals, HEIGHT, 0, fx.block_id)
        votes = [Vote(type=VoteType.PRECOMMIT, height=HEIGHT, round=0,
                      block_id=fx.block_id,
                      timestamp=BASE_TS + i * 1_000_003,
                      validator_address=v.address, validator_index=i,
                      signature=fx.bad_sigs[i])
                 for i, v in enumerate(fx.vals.validators)]
        half = fx.n // 2

        def arrive(batch):
            for v in batch:
                plane.observe_precommit(v)
            plane.flush_sync()

        _, first_s = _timed(lambda: arrive(votes[:half]))
        arena = plane._arena
        check(arena is not None, "the speculation plane built no arena")
        ptr0 = arena.buffer_pointer("sb")
        _, second_s = _timed(lambda: arrive(votes[half:]))
        ptr1 = arena.buffer_pointer("sb")
        lanes = plane._heights[HEIGHT].lanes
        got = np.asarray([lanes[i].verdict for i in range(fx.n)], bool)
        want = np.ones(fx.n, bool)
        want[fx.bad] = False
        check((got == want).all(), "arena lane verdicts differ from the "
              f"reference at {np.flatnonzero(got != want)[:8].tolist()}")
        return dict(
            arena_capacity=arena.capacity, arena_bytes=arena.arena_bytes(),
            arena_first_launch_s=first_s, arena_second_launch_s=second_s,
            arena_buffer_reused=(ptr0 == ptr1 if ptr0 is not None
                                 else None),
            arena_lanes_equal_reference=True)
    finally:
        plane.close()


def phases_b_c(size: dict, pool: RefPool, platform: str) -> None:
    from tendermint_tpu.crypto.tpu import expanded as ex
    from tendermint_tpu.crypto.tpu import ledger
    from tendermint_tpu.crypto.tpu import verify as tv

    fx, fixture_s = _timed(lambda: CommitFixture(size["validators"]))
    # the memoized jit wrappers and host tables, made once before the
    # tasks race to make them
    tv.b_comb_tables(), tv._kernel(), ex._skernel(), ex._builder()
    t0 = time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(6) as tp:
        tasks = {
            "commit": tp.submit(task_commit, fx),
            "light": tp.submit(task_commit_light, fx),
            "lanes": tp.submit(task_batch_lanes, fx, pool),
            "general": tp.submit(task_general, size, pool),
            "sr25519": tp.submit(task_sr25519, size, pool),
            "arena": tp.submit(task_arena, size, fx),
        }
        facts = {name: f.result() for name, f in tasks.items()}
    first_calls_s = round(time.perf_counter() - t0, 3)
    _assert_on_chip("B+C first calls", platform)

    # warm, one at a time
    verify_commit, verify_light = _commit_calls(fx)
    _, warm_s = _timed(lambda: verify_commit(fx.good))
    _, light_warm_s = _timed(lambda: verify_light(fx.good))
    fx.expect_rejected(verify_commit, "verify_commit (warm)")
    _assert_on_chip("B", platform)
    kernels = sorted({r["kernel"] for r in ledger.snapshot()
                      if r["kernel"] in ("structured", "expanded")})
    check(kernels == ["structured"],
          f"the structured path was not the one taken: {kernels}")
    emit("B", validators=fx.n, fixture_s=fixture_s, **facts["commit"],
         **facts["light"], verify_commit_warm_s=warm_s,
         verify_commit_light_warm_s=light_warm_s, **facts["lanes"],
         first_calls_concurrent_s=first_calls_s,
         compile_s_sum=_compile_seconds(),
         peak_bytes_in_use=_peak_bytes())
    emit("C", **facts["general"], **facts["sr25519"], **facts["arena"],
         launches={k: sum(1 for r in ledger.snapshot()
                          if r["kernel"] == k)
                   for k in ("general", "sr25519", "resident")},
         peak_bytes_in_use=_peak_bytes())


# ------------------------------------------------- phase D: four chips


def phase_d(size: dict, pool: RefPool, platform: str) -> None:
    import gc

    import jax
    import numpy as np

    from tendermint_tpu.crypto.tpu import expanded as ex
    from tendermint_tpu.crypto.tpu import ledger
    from tendermint_tpu.crypto.tpu import verify as tv
    from tendermint_tpu.types.sign_batch import CommitSignBatch

    devs = jax.devices()
    check(len(devs) == 4, f"--four-chips needs 4 devices, JAX has "
                          f"{len(devs)}")
    fx = CommitFixture(size["validators"])
    n, idx = fx.n, list(range(fx.n))
    ref = pool.verdicts("ed25519", fx.triples())
    want = np.ones(n, bool)
    want[fx.bad] = False
    check((ref == want).all(), "the host reference disagrees with how "
                               "the commit was corrupted")

    def in_use():
        return {str(d): (d.memory_stats() or {}).get("bytes_in_use")
                for d in devs}

    def lane_verdicts(exp):
        """The corrupted commit, all n lanes, in the structured form
        verify_commit launches (sign bytes assembled on device)."""
        return exp.verify_structured(
            idx, CommitSignBatch(CHAIN_ID, fx.corrupt, idx), fx.bad_sigs)

    # what the mesh results are compared with: the same inputs on ONE
    # device (tables on the default device, launches unsharded)
    base = in_use()
    real_mesh, tv._mesh = tv._mesh, lambda: None
    try:
        single, single_build_s = _timed(lambda: ex.ExpandedKeys(fx.pubs))
    finally:
        tv._mesh = real_mesh
    check(single.mesh is None and not single.sharded,
          "the single-device comparison is not single-device")
    v_single = lane_verdicts(single)
    check((v_single == ref).all(), "single-device lanes differ from "
                                   "ed25519_ref")
    single._release_hbm()
    del single
    gc.collect()
    emit("D.single", validators=n, build_s=single_build_s,
         lanes_equal_ed25519_ref=True, bytes_in_use_before=base,
         bytes_in_use_after_release=in_use())

    for regime, crossover in (("replicated", None),
                              ("sharded", size["shard_crossover"])):
        ex.set_shard_crossover(crossover)
        with ex._CACHE_LOCK:
            ex._CACHE.clear()
        gc.collect()
        n0 = len(ledger.snapshot())
        c0 = _compile_seconds()
        try:
            _, first_s = _timed(lambda: fx.vals.verify_commit(
                CHAIN_ID, fx.block_id, HEIGHT, fx.good))
            exp = ex.get_expanded(fx.pubs)
            check(exp.mesh is not None and exp.mesh.devices.size == 4
                  and exp.sharded == (regime == "sharded"),
                  f"tables are not {regime} over 4 devices: mesh="
                  f"{exp.mesh} sharded={exp.sharded}")
            after_build = in_use()
            _, warm_s = _timed(lambda: fx.vals.verify_commit(
                CHAIN_ID, fx.block_id, HEIGHT, fx.good))
            fx.expect_rejected(
                lambda c: fx.vals.verify_commit(
                    CHAIN_ID, fx.block_id, HEIGHT, c),
                f"verify_commit ({regime})")
            v = lane_verdicts(exp)
            check((v == v_single).all() and (v == ref).all(),
                  f"{regime} lanes differ from the single-device "
                  f"result at {np.flatnonzero(v != v_single)[:8].tolist()}")
            recs = ledger.snapshot()[n0:]
            spans = sorted({r["n_devices"] for r in recs})
            check(recs and spans == [4],
                  f"{regime} launches spanned {spans} devices, not 4")
            _assert_on_chip("D." + regime, platform)
            emit("D." + regime, validators=n,
                 table_bytes_per_device={
                     str(s.device): int(s.data.nbytes)
                     for s in exp.tables.addressable_shards},
                 bytes_in_use_after_build=after_build,
                 verify_commit_first_s=first_s,
                 verify_commit_warm_s=warm_s,
                 compile_s=round(_compile_seconds() - c0, 3),
                 kernels=sorted({r["kernel"] for r in recs}),
                 rejected_indexes=fx.bad,
                 lanes_equal_single_device=True,
                 lanes_equal_ed25519_ref=True)
            exp._release_hbm()
            del exp
        finally:
            ex.set_shard_crossover(None)


# ------------------------------------------------------------------ main


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="phase D only, on the four chips of one host")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever backend JAX has; "
                         "prints no result line")
    args = ap.parse_args()
    size = REHEARSE if args.rehearse else FULL

    from tendermint_tpu import native
    from tendermint_tpu.libs import jaxcache

    cache_dir = jaxcache.configure()
    # JAX is held to another platform: fail at once, before any child.
    # (Unset, the node child's `backend = "tpu"` refusal or the check
    # below decides — the parent may not open the chip before phase A.)
    held = os.environ.get("JAX_PLATFORMS", "").split(",")[0]
    platform = (held or "tpu") if args.rehearse else "tpu"
    check(args.rehearse or held in ("", "tpu"),
          f"no TPU: JAX_PLATFORMS={held!r}")
    emit("setup", rehearse=args.rehearse, four_chips=args.four_chips,
         compile_cache_dir=cache_dir,
         packer="native" if native.lib() is not None else "numpy")

    pool = RefPool()
    try:
        if not args.four_chips:
            phase_a(size, args.rehearse, platform)
        # only now may this process open the chip
        import jax

        dev = jax.devices()[0]
        if not args.rehearse:
            check(dev.platform == "tpu",
                  f"no TPU: the default JAX backend is {dev.platform!r} "
                  f"({dev.device_kind})")
        check(dev.platform == platform,
              f"expected platform {platform!r}, JAX has {dev.platform!r}")
        _watch_compiles()
        if args.four_chips:
            phase_d(size, pool, platform)
        else:
            phases_b_c(size, pool, platform)
        emit("done", compile_s_total=_compile_seconds(),
             peak_bytes_in_use=_peak_bytes())
        if args.rehearse:
            return 0
        print(json.dumps({"ok": True, "device": {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}}), flush=True)
        return 0
    finally:
        pool.close()


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
