"""Crypto micro-benchmarks (reference: crypto/internal/benchmarking/
bench.go + per-keytype bench_test.go files).

Keygen / sign / verify for every key type, host oracles and device
batch paths, printed as one table. Run on CPU for sanity or on the
real chip for numbers:

    python tools/crypto_bench.py [--cpu] [--batch N]

`--mesh N` runs the multi-chip fabric A/B instead (over an N-device
mesh — forced-host CPU devices unless GRAFT_REAL_DEVICES=1):
replicated vs key-range-sharded expanded tables, fresh-transfer vs
resident-shard relaunches, with per-launch per-device byte accounting,
emitted as one MULTICHIP-style JSON line (backend + n_devices stamped
so a CPU run can never pass as silicon). Add `--evict K` for the
degraded-fabric A/B: K devices are breaker-evicted, the live reshard
and the surviving-mesh verify are timed (verdicts asserted identical),
the evicted devices re-admit, and the active device set the launch
ledger recorded is stamped into the JSON line.
"""

import hashlib
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timeit(f, reps):
    t0 = time.perf_counter()
    for _ in range(reps):
        f()
    return (time.perf_counter() - t0) / reps


def _resident_ab(batch: int):
    """A/B the verify-ahead transfer story on real commit-shaped
    lanes: (a) a FRESH launch re-ships every lane's pubkey + signature
    + sign bytes (the general kernel path), (b) the ResidentArena
    splices a small per-height delta into donated device-resident
    buffers and relaunches. Prints per-launch latency plus the bytes
    each path actually uploads."""
    import numpy as np

    from tendermint_tpu.crypto import ed25519_ref as ref
    from tendermint_tpu.crypto.tpu import verify as tv
    from tendermint_tpu.crypto.tpu.resident import ResidentArena
    from tendermint_tpu.types import canonical, sign_batch as sbm
    from tendermint_tpu.types.vote import VoteType

    n = batch
    delta = max(1, min(64, n // 16))
    seeds = [hashlib.sha256(b"res%d" % i).digest() for i in range(n)]
    pubs = [ref.public_key_from_seed(s) for s in seeds]
    from tendermint_tpu.types.block import BlockID, PartSetHeader

    bid = BlockID(b"\xab" * 32, PartSetHeader(4, b"\xcd" * 32))
    pre, suf = canonical.vote_sign_parts(
        "bench-chain", int(VoteType.PRECOMMIT), 123456, 0, bid)
    base_ts = 1_753_928_000_000_000_000
    ts = np.asarray([base_ts + i * 1_000_003 for i in range(n)],
                    np.int64)
    msgs = [canonical.vote_sign_bytes(
        "bench-chain", int(VoteType.PRECOMMIT), 123456, 0, bid,
        int(t)) for t in ts]
    sigs = [ref.sign(s, m) for s, m in zip(seeds, msgs)]

    arena = ResidentArena(n + 1)
    arena.install_keys(pubs)
    arena.set_template(1, pre, suf)
    group = np.ones(n, np.int32)
    patch, split, patch_len = sbm._build_patches(
        arena.pre_len.astype(np.int64), arena.suf_len, group, ts)
    sig_rows = np.frombuffer(b"".join(sigs), np.uint8).reshape(n, 64)
    slots = list(range(1, n + 1))
    arena.splice(slots, sig_rows, patch, split, patch_len, group)
    out = arena.launch()  # compile + warm
    assert bool(out[0]) and bool(out[1:n + 1].all()), \
        "resident arena lanes must verify"
    tv.verify_batch(pubs[:n], msgs[:n], sigs[:n])  # warm fresh path

    def resident_relaunch():
        lo = arena.reupload_bytes
        arena.splice(slots[:delta], sig_rows[:delta], patch[:delta],
                     split[:delta], patch_len[:delta], group[:delta])
        arena.launch()
        return arena.reupload_bytes - lo

    fresh_bytes = n * (32 + 64) + sum(len(m) for m in msgs)
    t_fresh = timeit(
        lambda: tv.verify_batch(pubs, msgs, sigs), 3)
    lo = arena.reupload_bytes
    t_res = timeit(resident_relaunch, 3)
    res_bytes = (arena.reupload_bytes - lo) // 3
    print(f"resident A/B x{n}: fresh ~{fresh_bytes} B/launch, "
          f"resident delta={delta} lanes ~{res_bytes} B/launch "
          f"({fresh_bytes / max(res_bytes, 1):.0f}x less transfer)")
    return [
        (f"ed25519 fresh-transfer launch x{n}", t_fresh),
        (f"ed25519 resident relaunch x{n} (delta {delta})", t_res),
    ]


def _commit_lanes(n, n_keys):
    """Commit-shaped lanes over a fixed valset: (pubs, idx, msgs,
    sigs) with real canonical vote sign bytes."""
    import numpy as np

    from tendermint_tpu.crypto import ed25519_ref as ref
    from tendermint_tpu.types import canonical
    from tendermint_tpu.types.block import BlockID, PartSetHeader
    from tendermint_tpu.types.vote import VoteType

    seeds = [hashlib.sha256(b"mesh%d" % i).digest()
             for i in range(n_keys)]
    pubs = [ref.public_key_from_seed(s) for s in seeds]
    bid = BlockID(b"\xab" * 32, PartSetHeader(4, b"\xcd" * 32))
    base_ts = 1_753_928_000_000_000_000
    idx = np.asarray([i % n_keys for i in range(n)], np.int32)
    msgs = [canonical.vote_sign_bytes(
        "bench-chain", int(VoteType.PRECOMMIT), 123456, 0, bid,
        base_ts + i * 1_000_003) for i in range(n)]
    sigs = [ref.sign(seeds[idx[i]], m) for i, m in enumerate(msgs)]
    return pubs, idx, msgs, sigs


def _mesh_ab(batch: int, evict: int = 0) -> int:
    """The multi-chip fabric A/B: replicated vs key-range-sharded
    expanded tables and fresh-transfer vs per-device resident-shard
    relaunches, with per-launch per-device byte accounting. Prints a
    MULTICHIP-style JSON line as the final output."""
    import json

    import numpy as np

    import jax

    from tendermint_tpu.crypto.tpu import expanded as ex
    from tendermint_tpu.crypto.tpu import verify as tv
    from tendermint_tpu.crypto.tpu.resident import (
        MeshResidentArena, ResidentArena,
    )
    from tendermint_tpu.types import sign_batch as sbm

    from tendermint_tpu.crypto.tpu.backend import backend_label

    device = str(jax.devices()[0])
    line = {
        "metric": "multichip_crypto_bench",
        "backend": backend_label(device),
        "n_devices": jax.device_count(),
        "device": device,
        "ok": False,
    }
    mesh = tv._mesh()
    if mesh is None:
        line["error"] = "no multi-device mesh (need --mesh N >= 2)"
        print(json.dumps(line), flush=True)
        return 2
    d_n = int(mesh.devices.size)
    n = batch
    n_keys = max(d_n * 16, min(n, 256))
    pubs, idx, msgs, sigs = _commit_lanes(n, n_keys)
    idx_l = list(idx)
    line.update(lanes=n, keys=n_keys)

    # -- A: replicated tables (the pre-fabric production path) --
    ex.set_shard_crossover(None)
    try:
        repl = ex.ExpandedKeys(pubs)
        assert not repl.sharded
        want = repl.verify(idx_l, msgs, sigs)
        assert bool(np.asarray(want).all())
        t_repl = timeit(lambda: repl.verify(idx_l, msgs, sigs), 3)
        line["replicated_p50_ms"] = round(t_repl * 1e3, 3)
        line["replicated_table_bytes_per_device"] = int(
            repl.tables.nbytes)

        # -- B: key-range-sharded tables + lane routing --
        ex.set_shard_crossover(1)
        shd = ex.ExpandedKeys(pubs)
        assert shd.sharded and shd.n_shards == d_n
        got = shd.verify(idx_l, msgs, sigs)
        assert (np.asarray(got) == np.asarray(want)).all(), \
            "sharded verdicts diverged from replicated"
        t_shd = timeit(lambda: shd.verify(idx_l, msgs, sigs), 3)
        line["sharded_p50_ms"] = round(t_shd * 1e3, 3)
        line["sharded_table_bytes_per_device"] = int(
            shd.tables.nbytes) // d_n
        line["sharded_lanes_per_device"] = [
            int(c) for c in np.bincount(idx // shd.keys_per_shard,
                                        minlength=d_n)]

        # -- D (--evict K): degraded-mesh A/B — evict K devices, time
        # the live reshard + the degraded fabric, re-admit, and stamp
        # the active device set the ledger recorded --
        if evict:
            from tendermint_tpu.crypto import batch as cbatch
            from tendermint_tpu.crypto.tpu import ledger as tpu_ledger

            assert 0 < evict < d_n - 1, \
                "--evict K needs at least 2 surviving devices"
            victims = [str(d) for d in mesh.devices.flat][-evict:]
            cbatch.mark_device_failed("ed25519", device=victims,
                                      reason="bench")
            t0 = time.perf_counter()
            deg = shd.verify(idx_l, msgs, sigs)  # reshards inline
            reshard_launch_s = time.perf_counter() - t0
            assert shd.n_shards == d_n - evict
            assert (np.asarray(deg) == np.asarray(want)).all(), \
                "degraded-mesh verdicts diverged"
            t_deg = timeit(lambda: shd.verify(idx_l, msgs, sigs), 3)
            active = next(
                (r["active_devices"]
                 for r in reversed(tpu_ledger.snapshot())
                 if r.get("active_devices")), None)
            for v in victims:
                cbatch.readmit_device("ed25519", v)
            t0 = time.perf_counter()
            back = shd.verify(idx_l, msgs, sigs)  # reshards back
            readmit_launch_s = time.perf_counter() - t0
            assert shd.n_shards == d_n
            assert (np.asarray(back) == np.asarray(want)).all(), \
                "re-admitted-mesh verdicts diverged"
            line["degraded"] = {
                "evicted": victims,
                "degraded_p50_ms": round(t_deg * 1e3, 3),
                "full_p50_ms": line["sharded_p50_ms"],
                "reshard_first_launch_ms": round(
                    reshard_launch_s * 1e3, 3),
                "readmit_first_launch_ms": round(
                    readmit_launch_s * 1e3, 3),
                "active_devices": active,
            }
    finally:
        ex.set_shard_crossover(None)
        if evict:
            from tendermint_tpu.crypto import batch as cbatch

            cbatch.reset_breakers()

    # -- C: fresh-transfer vs per-device resident-shard relaunch --
    delta = max(1, min(64, n // 16))
    fresh_bytes = n * (32 + 64) + sum(len(m) for m in msgs)
    arena = MeshResidentArena(n + 1, mesh=mesh)
    single = ResidentArena(n + 1)
    from tendermint_tpu.types import canonical
    from tendermint_tpu.types.block import BlockID, PartSetHeader
    from tendermint_tpu.types.vote import VoteType

    bid = BlockID(b"\xab" * 32, PartSetHeader(4, b"\xcd" * 32))
    pre, suf = canonical.vote_sign_parts(
        "bench-chain", int(VoteType.PRECOMMIT), 123456, 0, bid)
    base_ts = 1_753_928_000_000_000_000
    ts = np.asarray([base_ts + i * 1_000_003 for i in range(n)],
                    np.int64)
    group = np.ones(n, np.int32)
    for a in (arena, single):
        a.set_template(1, pre, suf)
    patch, split, patch_len = sbm._build_patches(
        arena.pre_len.astype(np.int64), arena.suf_len, group, ts)
    sig_rows = np.frombuffer(b"".join(sigs), np.uint8).reshape(n, 64)
    slots = list(range(1, n + 1))
    for a in (arena, single):
        a.splice(slots, sig_rows, patch, split, patch_len, group)
    lo_single = single.reupload_bytes
    single.splice(slots[:delta], sig_rows[:delta], patch[:delta],
                  split[:delta], patch_len[:delta], group[:delta])
    single_delta = single.reupload_bytes - lo_single
    lo_shards = arena.shard_reupload_bytes()
    arena.splice(slots[:delta], sig_rows[:delta], patch[:delta],
                 split[:delta], patch_len[:delta], group[:delta])
    per_dev = [hi - lo for hi, lo in
               zip(arena.shard_reupload_bytes(), lo_shards)]
    line["resident"] = {
        "fresh_bytes_per_launch": fresh_bytes,
        "delta_lanes": delta,
        "single_device_delta_bytes": int(single_delta),
        "shard_delta_bytes_per_device": [int(b) for b in per_dev],
        "max_shard_delta_bytes": int(max(per_dev)),
    }
    line["ok"] = True
    print(json.dumps(line), flush=True)
    return 0


def main():
    mesh_n = 0
    if "--mesh" in sys.argv:
        # Env must land before the first jax import: force an N-device
        # host-platform mesh unless the caller wants real chips.
        mesh_n = int(sys.argv[sys.argv.index("--mesh") + 1])
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags
                + f" --xla_force_host_platform_device_count={mesh_n}"
            ).strip()
        if not os.environ.get("GRAFT_REAL_DEVICES"):
            os.environ["JAX_PLATFORMS"] = "cpu"
    if "--cpu" in sys.argv:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from tendermint_tpu.libs import jaxcache

    jaxcache.configure()
    batch = 1024
    evict = 0
    for i, a in enumerate(sys.argv):
        if a == "--batch":
            batch = int(sys.argv[i + 1])
        elif a == "--evict":
            evict = int(sys.argv[i + 1])
    if mesh_n:
        sys.exit(_mesh_ab(batch, evict=evict))

    rows = []

    # -- ed25519 --
    from tendermint_tpu.crypto import ed25519

    priv = ed25519.Ed25519PrivKey.generate()
    pub = priv.pub_key()
    msg = b"bench message for signing"
    sig = priv.sign(msg)
    rows.append(("ed25519 keygen", timeit(
        ed25519.Ed25519PrivKey.generate, 200)))
    rows.append(("ed25519 sign", timeit(lambda: priv.sign(msg), 200)))
    rows.append(("ed25519 verify (host)", timeit(
        lambda: pub.verify_signature(msg, sig), 200)))

    # -- sr25519 --
    from tendermint_tpu.crypto import sr25519_ref as sr

    mini = hashlib.sha256(b"bench").digest()
    spub = sr.public_key_from_mini(mini)
    ssig = sr.sign(mini, msg)
    rows.append(("sr25519 sign (host)", timeit(
        lambda: sr.sign(mini, msg), 5)))
    rows.append(("sr25519 verify (host)", timeit(
        lambda: sr.verify(spub, msg, ssig), 5)))

    # -- secp256k1 --
    from tendermint_tpu.crypto import secp256k1 as secp

    kpriv = secp.Secp256k1PrivKey.generate()
    kpub = kpriv.pub_key()
    ksig = kpriv.sign(msg)
    rows.append(("secp256k1 sign", timeit(lambda: kpriv.sign(msg), 20)))
    rows.append(("secp256k1 verify", timeit(
        lambda: kpub.verify_signature(msg, ksig), 20)))

    # -- batched device paths --
    from tendermint_tpu.crypto.tpu import verify as tv
    from tendermint_tpu.crypto.tpu.sr_verify import verify_batch_sr

    seeds = [hashlib.sha256(b"b%d" % i).digest() for i in range(batch)]
    from tendermint_tpu.crypto import ed25519_ref as ref

    pubs = [ref.public_key_from_seed(s) for s in seeds]
    msgs = [b"bench %d" % i for i in range(batch)]
    sigs = [ref.sign(s, m) for s, m in zip(seeds, msgs)]
    tv.verify_batch(pubs, msgs, sigs)  # compile
    t = timeit(lambda: tv.verify_batch(pubs, msgs, sigs), 3)
    rows.append((f"ed25519 device batch x{batch} (per sig)", t / batch))

    n_sr = min(batch, 256)
    minis = [hashlib.sha256(b"s%d" % i).digest() for i in range(n_sr)]
    spubs = [sr.public_key_from_mini(m) for m in minis]
    ssigs = [sr.sign(m, mm) for m, mm in zip(minis, msgs[:n_sr])]
    verify_batch_sr(spubs, msgs[:n_sr], ssigs)  # compile
    t = timeit(lambda: verify_batch_sr(spubs, msgs[:n_sr], ssigs), 3)
    rows.append((f"sr25519 device batch x{n_sr} (per sig)", t / n_sr))

    # -- resident-arena A/B: donated device-resident buffers vs fresh
    # full-transfer launches over the same commit-shaped lanes --
    if "--resident" in sys.argv:
        rows.extend(_resident_ab(batch))

    import jax

    device = str(jax.devices()[0])
    print(f"device: {device}")
    width = max(len(r[0]) for r in rows)
    for name, secs in rows:
        print(f"{name:<{width}}  {secs * 1e6:>12.1f} us")


if __name__ == "__main__":
    main()
