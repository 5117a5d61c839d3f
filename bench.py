"""Headline benchmark: 10k-validator Commit signature verification.

Prints JSON lines; the LAST line is the result the driver records:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

The metric is p50 latency of verifying a 10,240-signature commit batch
(10k validators, BASELINE.json config #5) on the default JAX device.
vs_baseline = speedup over the reference's execution model: a
sequential single-core CPU verify loop (types/validator_set.go:683-705)
measured here with OpenSSL ed25519 (a *fast* CPU baseline — the
reference's pure-Go verifier is slower).

One process, which owns the chip. It fails at once when the default
JAX backend is not a TPU (a CPU timing is never printed under the
device metric's name), every line carries platform, device_kind and
device count, and after every stage the launch ledger is read: a
launch that landed off the chip, raised or degraded to the host ends
the run non-zero (tools/bench_util.chip_faults). Stages run small ->
large, each line superseding the last.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

METRIC = "ed25519_commit_verify_p50_10k_vals"


def _emit(d):
    print(json.dumps(d), flush=True)


def ledger_rollup():
    """Per-workload launch-ledger rollup (launch count, lanes, bytes,
    backend mix, exec p50/p99 — crypto/tpu/ledger.py) embedded in
    every measured BENCH line: the line itself then carries the
    evidence of WHERE its launches ran, next to the backend stamp."""
    from tendermint_tpu.crypto.tpu import ledger as tpu_ledger

    return tpu_ledger.rollup()["workloads"]


def _measure(fn, reps, warmed=False):
    if not warmed:
        fn()
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


def main():
    import hashlib

    from tendermint_tpu.libs import jaxcache

    jaxcache.configure()
    import jax

    dev0 = jax.devices()[0]
    if dev0.platform != "tpu":
        sys.exit(f"bench.py measures the chip: the default JAX backend "
                 f"is {dev0.platform!r} ({dev0.device_kind}), not a TPU")

    import numpy as np  # noqa: F401  (keeps import cost out of timings)

    from tendermint_tpu.crypto.tpu import expanded as ex
    from tendermint_tpu.crypto.tpu import verify as tv

    n = 10240  # 10k validators, one CommitSig each
    for arg in sys.argv:
        if arg.startswith("--batch="):
            n = int(arg.split("=", 1)[1])
    baseline_estimated = False
    try:
        from cryptography.hazmat.primitives import serialization
        from cryptography.hazmat.primitives.asymmetric.ed25519 import (
            Ed25519PrivateKey,
        )

        keys = [
            Ed25519PrivateKey.from_private_bytes(
                hashlib.sha256(b"bench%d" % i).digest()
            )
            for i in range(n)
        ]
        pubs = [
            k.public_key().public_bytes(
                serialization.Encoding.Raw, serialization.PublicFormat.Raw
            )
            for k in keys
        ]
        msgs = [b"precommit h=1234 r=0 block=deadbeef val=%d" % i for i in range(n)]
        sigs = [k.sign(m) for k, m in zip(keys, msgs)]

        def sign_fn(i, m):
            return keys[i].sign(m)

        # CPU baseline: sequential strict verify, single core (OpenSSL).
        sample = min(256, n)
        t0 = time.perf_counter()
        for i in range(sample):
            keys[i].public_key().verify(sigs[i], msgs[i])
        cpu_per_sig = (time.perf_counter() - t0) / sample
    except ImportError:  # pragma: no cover
        baseline_estimated = True
        from tendermint_tpu.crypto import ed25519_ref as ref

        pubs, msgs, sigs = [], [], []
        seeds = []
        for i in range(n):
            seed = hashlib.sha256(b"bench%d" % i).digest()
            seeds.append(seed)
            pubs.append(ref.public_key_from_seed(seed))
            msgs.append(b"precommit %d" % i)
            sigs.append(ref.sign(seed, msgs[-1]))
        cpu_per_sig = 100e-6  # nominal estimate, flagged below

        def sign_fn(i, m):
            return ref.sign(seeds[i], m)

    from tendermint_tpu.libs import metrics as tmetrics
    from tendermint_tpu.libs.tracing import TRACER
    from tools.bench_util import chip_faults, pipelined_exec_s

    def stage_breakdown():
        """Per-stage p50/p95/p99 rollup of the crypto AND speculation
        spans recorded since the last TRACER.clear(): device-exec vs
        host-pack vs dispatch/readback attribution — plus the
        verify-ahead speculate/patch/reconcile stages — rides in every
        BENCH line instead of a single end-to-end number."""
        roll = TRACER.stage_rollup(prefix="crypto.")
        roll.update(TRACER.stage_rollup(prefix="speculation."))
        return roll

    def metrics_before():
        """Snapshot the process /metrics registry before a measured
        stage; the delta (counter increments + histogram quantiles,
        incl. the bridge-fed tpu_* stage histograms) rides in the
        BENCH line next to stage_breakdown, so the perf trajectory
        records device telemetry per run."""
        return tmetrics.snapshot()

    def metrics_delta(before):
        return tmetrics.delta(before, tmetrics.snapshot())

    from tendermint_tpu.crypto.tpu import ledger as tpu_ledger
    from tendermint_tpu.crypto.tpu.backend import backend_label

    # every kernel launch below lands in the launch ledger under the
    # "bench" workload (process-lifetime tag: the process IS the bench)
    tpu_ledger.workload("bench").__enter__()

    device = str(dev0)
    common = {
        "metric": METRIC,
        "unit": "ms",
        # where it ran, on EVERY line: platform, device_kind and
        # device count as JAX reports them, plus the device string
        # and the backend stamp tools/bench_trend.py classifies by
        "platform": dev0.platform,
        "device_kind": dev0.device_kind,
        "n_devices": jax.device_count(),
        "device": device,
        "backend": backend_label(device),
        "cpu_baseline_us_per_sig": round(cpu_per_sig * 1e6, 1),
        "baseline_estimated": baseline_estimated,
    }

    def emit(line):
        """Print a stage's line — unless a launch so far landed off
        the chip, raised or degraded to the host: then the run fails
        and the line is not a device number."""
        faults = chip_faults()
        if faults:
            sys.exit("bench.py: host fallback on the device path: "
                     + "; ".join(faults))
        _emit(line)

    def pipelined(launch, pidx, packed, in_order):
        """Device-only ms/launch, excluding the per-call host round
        trip and input transfer: inputs device_put once, then the
        two-burst slope from tools/bench_util isolates execution."""
        pidx = jax.device_put(pidx)
        packed = {kk: jax.device_put(v) for kk, v in packed.items()}
        return pipelined_exec_s(lambda: launch(pidx, packed, in_order))

    # PRODUCT HOT PATH: ValidatorSet.verify_commit* routes big commits
    # through per-validator comb tables cached on device across heights
    # (crypto/tpu/expanded.py) — the valset is known in advance in
    # consensus, so the table build (once per valset change in the
    # node) is warm-up, not latency.

    # Stage 1: 1,024 lanes (BASELINE config #3, fast-sync block at 1k
    # validators). Small table build, fast compile. Its line carries
    # its own measurement only — no value under the 10k metric.
    n1k = min(1024, n)
    exp1k = ex.get_expanded(pubs[:n1k])
    idx1k = list(range(n1k))
    assert bool(exp1k.verify(idx1k, msgs[:n1k], sigs[:n1k]).all())
    TRACER.clear()  # rollup covers the measured reps only, not warm-up
    m0 = metrics_before()
    p50_1k = _measure(
        lambda: exp1k.verify(idx1k, msgs[:n1k], sigs[:n1k]), 7, warmed=True)
    pidx1k, packed1k, _wf, slots = exp1k._prepare(
        idx1k, msgs[:n1k], sigs[:n1k])
    dev1k, single1k, _tot = pipelined(exp1k._launch, pidx1k, packed1k,
                                      slots is not None)
    emit({
        **common,
        "value": None,
        "batch": n1k,
        "expanded_valset": True,
        "fastsync_block_1k_vals_p50_ms": round(p50_1k * 1e3, 3),
        "sigs_per_sec": round(n1k / p50_1k),
        "device_exec_ms_per_launch": (
            round(dev1k * 1e3, 3) if dev1k else None),
        "single_launch_synced_ms": round(single1k * 1e3, 3),
        "stage_breakdown": stage_breakdown(),
        "metrics_delta": metrics_delta(m0),
        "ledger_rollup": ledger_rollup(),
    })
    if n <= n1k:
        return

    # Stage 2: the full 10,240-lane commit.
    exp = ex.get_expanded(pubs)
    idx = list(range(n))
    assert bool(exp.verify(idx, msgs, sigs).all()), "bench batch must verify"
    TRACER.clear()
    m0 = metrics_before()
    p50 = _measure(lambda: exp.verify(idx, msgs, sigs), 7, warmed=True)
    line = {
        **common,
        "value": round(p50 * 1e3, 3),
        "vs_baseline": round(cpu_per_sig * n / p50, 2),
        "sigs_per_sec": round(n / p50),
        "batch": n,
        "expanded_valset": True,
        "stage_breakdown": stage_breakdown(),
        "metrics_delta": metrics_delta(m0),
        "ledger_rollup": ledger_rollup(),
    }
    emit(line)

    # Host/device breakdown of the same path: host = packing/padding
    # (numpy), device = kernel launch to synced verdict on the packed
    # arrays. They do not sum exactly to p50 (transfer overlap), but
    # bound where the time goes.
    pidx, packed, _wf, slots = exp._prepare(idx, msgs, sigs)
    in_order = slots is not None
    host_ms = _measure(lambda: exp._prepare(idx, msgs, sigs), 5,
                       warmed=True) * 1e3
    dev_ms = _measure(
        lambda: exp._launch(pidx, packed, in_order).block_until_ready(),
        5) * 1e3
    line["host_pack_p50_ms"] = round(host_ms, 3)
    line["device_p50_ms"] = round(dev_ms, 3)
    dev_pipe, dev_single, _tot = pipelined(exp._launch, pidx, packed,
                                           in_order)
    line["device_exec_ms_per_launch"] = (
        round(dev_pipe * 1e3, 3) if dev_pipe else None)
    line["single_launch_synced_ms"] = round(dev_single * 1e3, 3)
    if dev_pipe:
        # Pure device throughput with launches in flight — the
        # production vote-scheduler shape (batches pipeline behind
        # one sync; host pack overlaps the previous launch).
        line["device_sigs_per_sec_pipelined"] = round(n / dev_pipe)

    # Fast-sync through the WARM 10k tables (1k-lane subset).
    exp.verify(idx1k, msgs[:n1k], sigs[:n1k])  # shape warm-up
    block_1k_p50 = _measure(
        lambda: exp.verify(idx1k, msgs[:n1k], sigs[:n1k]), 5, warmed=True)
    line["fastsync_block_1k_vals_p50_ms"] = round(block_1k_p50 * 1e3, 3)
    emit(line)

    # Stage 3: a REAL 10,240-signature commit through the structured
    # path — sign bytes assembled ON DEVICE from the commit-wide
    # template + per-lane timestamp patch (types/sign_batch.py), the
    # production route for ValidatorSet.verify_commit*. Unlike stage
    # 2's short synthetic messages this is full ~187-byte canonical
    # vote sign bytes, and the measured fn includes the per-commit
    # CommitSignBatch host build. Runs BEFORE any optional extra —
    # its line supersedes stage 2 as the recorded headline and is
    # re-emitted at the very end so it stays the tail.
    from tendermint_tpu.types.block import (
        BlockID, BlockIDFlag, Commit, CommitSig, PartSetHeader,
    )
    from tendermint_tpu.types.sign_batch import CommitSignBatch

    bid = BlockID(hash=b"\xab" * 32,
                  part_set_header=PartSetHeader(4, b"\xcd" * 32))
    base_ts = 1_753_928_000_000_000_000
    cs = [CommitSig(BlockIDFlag.COMMIT,
                    hashlib.sha256(b"a%d" % i).digest()[:20],
                    base_ts + i * 1_000_003, b"")
          for i in range(n)]
    commit = Commit(height=123456, round=0, block_id=bid, signatures=cs)
    idxs = list(range(n))
    csigs = []
    for i in range(n):
        sig = sign_fn(i, commit.vote_sign_bytes("bench-chain", i))
        cs[i].signature = sig
        csigs.append(sig)
    assert bool(exp.verify_structured(
        idxs, CommitSignBatch("bench-chain", commit, idxs), csigs).all())

    def run_structured():
        sb = CommitSignBatch("bench-chain", commit, idxs)
        return exp.verify_structured(idxs, sb, csigs)

    TRACER.clear()
    m0 = metrics_before()
    p50_s = _measure(run_structured, 7, warmed=True)
    stages_structured = stage_breakdown()
    mdelta_structured = metrics_delta(m0)
    # The recorded headline is the BEST product path for THIS real
    # commit, compared apples-to-apples: the bytes path timed on the
    # SAME ~187-byte canonical sign bytes (stage 2's number above used
    # short synthetic messages — 1 SHA block vs ~2 — and is kept
    # separately as synthetic_msgs_p50_ms).
    real_msgs = [commit.vote_sign_bytes("bench-chain", i)
                 for i in range(n)]
    exp.verify(idxs, real_msgs, csigs)  # shape warm-up
    p50_b = _measure(lambda: exp.verify(idxs, real_msgs, csigs),
                     5, warmed=True)
    structured_wins = p50_s < p50_b
    p50_best = min(p50_s, p50_b)
    line_s = {
        **common,
        "value": round(p50_best * 1e3, 3),
        "vs_baseline": round(cpu_per_sig * n / p50_best, 2),
        "sigs_per_sec": round(n / p50_best),
        "batch": n,
        "expanded_valset": True,
        "structured_commit": True,
        "winner": "structured" if structured_wins else "bytes",
        "note": "real %d-sig commit; best of structured "
                "(device-assembled sign bytes) vs bytes path on the "
                "same commit" % n,
        "fastsync_block_1k_vals_p50_ms":
            line.get("fastsync_block_1k_vals_p50_ms"),
        "bytes_path_p50_ms": round(p50_b * 1e3, 3),
        "structured_path_p50_ms": round(p50_s * 1e3, 3),
        "synthetic_msgs_p50_ms": line["value"],
        "device_exec_ms_per_launch":
            line.get("device_exec_ms_per_launch"),
        "stage_breakdown": stages_structured,
        "metrics_delta": mdelta_structured,
        "ledger_rollup": ledger_rollup(),
    }
    emit(line_s)

    # Stage 4: the verify-ahead pipeline over the SAME real commit —
    # precommits observed one by one, the speculative launch running
    # through the donated-buffer ResidentArena BEFORE the commit is
    # assembled, then the commit-time serve (reconcile-only on a hit).
    # spec_hit_ratio / overlap_ms / resident_reupload_bytes decompose
    # what moved off the critical path; the line_s re-emit keeps the
    # structured number the recorded tail.
    from tendermint_tpu.config import SpeculationConfig
    from tendermint_tpu.consensus.speculation import (
        SpeculationPlane,
    )
    from tendermint_tpu.crypto.ed25519 import Ed25519PubKey
    from tendermint_tpu.types.validator import Validator
    from tendermint_tpu.types.validator_set import ValidatorSet
    from tendermint_tpu.types.vote import Vote, VoteType

    addr_to_i = {Ed25519PubKey(p).address(): i
                 for i, p in enumerate(pubs)}
    vals = ValidatorSet(
        [Validator.new(Ed25519PubKey(p), 1) for p in pubs])
    spec_h = 123457
    plane = SpeculationPlane(
        SpeculationConfig(arena_lanes=n + 64))
    TRACER.clear()
    plane.begin_height("bench-chain", vals, spec_h, 0, bid)
    votes, spec_cs = [], []
    for idx, val in enumerate(vals.validators):
        ts = base_ts + idx * 1_000_003
        v = Vote(type=VoteType.PRECOMMIT, height=spec_h,
                 round=0, block_id=bid, timestamp=ts,
                 validator_address=val.address,
                 validator_index=idx)
        v.signature = sign_fn(addr_to_i[val.address],
                              v.sign_bytes("bench-chain"))
        votes.append(v)
        spec_cs.append(CommitSig(BlockIDFlag.COMMIT,
                                 val.address, ts, v.signature))
    t0 = time.perf_counter()
    for v in votes:
        plane.observe_precommit(v)
    plane.flush_sync()
    spec_launch_ms = (time.perf_counter() - t0) * 1e3
    commit_s = Commit(height=spec_h, round=0, block_id=bid,
                      signatures=spec_cs)
    entry = plane._heights[spec_h]
    overlap_ms = (time.monotonic() - entry.launch_done) * 1e3 \
        if entry.launch_done else None
    t0 = time.perf_counter()
    assert plane.serve_commit(vals, "bench-chain", bid, spec_h,
                              commit_s)
    serve_ms = (time.perf_counter() - t0) * 1e3
    lane_misses = sum(v for k, v in plane.misses.items()
                      if k != "no_plan")
    arena = plane._arena
    line_s["spec_hit_ratio"] = round((n - lane_misses) / n, 4)
    line_s["spec_launch_ms"] = round(spec_launch_ms, 3)
    line_s["spec_serve_ms"] = round(serve_ms, 3)
    line_s["overlap_ms"] = (round(overlap_ms, 3)
                            if overlap_ms is not None else None)
    line_s["resident_reupload_bytes"] = (
        arena.reupload_bytes if arena is not None else 0)
    line_s["spec_stage_breakdown"] = stage_breakdown()
    # Height-forensics rollup on the record: full consensus-
    # kind breakdown of the measured window + trace-ring
    # health, so a truncated ring can never pass silently as
    # a complete stage attribution (tools/forensics.py is the
    # cross-node reader of the same data).
    line_s["trace_rollup"] = TRACER.stage_rollup(
        prefix="consensus.")
    line_s["trace_ring"] = {
        "capacity": TRACER.capacity,
        "len": len(TRACER),
        "dropped": TRACER.dropped,
    }
    line_s["ledger_rollup"] = ledger_rollup()
    emit(line_s)

    # The general kernel — unknown keys, e.g. a light client's first
    # contact.
    assert bool(tv.verify_batch(pubs, msgs, sigs).all())
    cold_p50 = _measure(lambda: tv.verify_batch(pubs, msgs, sigs),
                        5, warmed=True)
    line_s["cold_keys_p50_ms"] = round(cold_p50 * 1e3, 3)
    emit(line_s)


if __name__ == "__main__":
    main()
