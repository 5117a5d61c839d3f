"""Measure host-vs-device crossovers and emit docs/THRESHOLDS.md.

Sweeps, on the current default JAX device:
  - host OpenSSL strict verify (the BatchVerifier host path)
  - the general device kernel (verify_batch) across batch sizes
  - the expanded-valset kernel across batch sizes (tables prebuilt)
  - sr25519: pure-host oracle vs the device batch kernel

and derives the data-driven settings VERDICT r2 weak #3 asked for:
  crypto/batch.py _DEVICE_THRESHOLD   (host->device crossover)
  validator_set _EXPAND_MIN           (general->expanded crossover)
  config vote_batch_window_ms         (~device launch latency)

Usage:  python tools/sweep_thresholds.py [--cpu] [--out docs/THRESHOLDS.md]
(--cpu forces the CPU backend — useful to smoke the tool, numbers are
then NOT meaningful for tuning and the doc is marked accordingly.)
"""

import hashlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SIZES = [16, 32, 64, 128, 256, 512, 1024, 2048, 4096, 10240]
SR_SIZES = [16, 64, 256, 1024]
REPS = 5


def p50(f, reps=REPS):
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        f()
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


def main():
    global SIZES, SR_SIZES
    cpu = "--cpu" in sys.argv
    out_path = "docs/THRESHOLDS.md"
    for i, a in enumerate(sys.argv):
        if a == "--out":
            out_path = sys.argv[i + 1]
        elif a == "--sizes":
            SIZES = [int(x) for x in sys.argv[i + 1].split(",")]
        elif a == "--sr-sizes":
            SR_SIZES = [int(x) for x in sys.argv[i + 1].split(",")]
    if cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from tendermint_tpu.libs import jaxcache

    jaxcache.configure()
    import jax

    device = str(jax.devices()[0])
    print(f"device: {device}", flush=True)

    from cryptography.hazmat.primitives import serialization
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
    )

    n_max = max(SIZES)
    keys = [Ed25519PrivateKey.from_private_bytes(
        hashlib.sha256(b"sw%d" % i).digest()) for i in range(n_max)]
    pubs = [k.public_key().public_bytes(
        serialization.Encoding.Raw, serialization.PublicFormat.Raw)
        for k in keys]
    msgs = [b"precommit h=99 r=0 val=%d" % i for i in range(n_max)]
    sigs = [k.sign(m) for k, m in zip(keys, msgs)]

    results = {"device": device, "cpu_forced": cpu,
               "ed25519": {}, "sr25519": {}}

    # host strict path per-sig
    sample = min(512, n_max)
    t0 = time.perf_counter()
    for i in range(sample):
        keys[i].public_key().verify(sigs[i], msgs[i])
    host_per_sig = (time.perf_counter() - t0) / sample
    results["ed25519"]["host_us_per_sig"] = round(host_per_sig * 1e6, 2)
    print(f"host: {host_per_sig * 1e6:.1f} us/sig", flush=True)

    from tendermint_tpu.crypto.tpu import expanded as ex
    from tendermint_tpu.crypto.tpu import verify as tv

    exp = ex.get_expanded(pubs)  # build once (warm-up, like the node)
    for n in SIZES:
        p, m_, s = pubs[:n], msgs[:n], sigs[:n]
        tv.verify_batch(p, m_, s)  # compile
        g = p50(lambda: tv.verify_batch(p, m_, s))
        idx = list(range(n))
        exp.verify(idx, m_, s)  # compile
        e = p50(lambda: exp.verify(idx, m_, s))
        results["ed25519"][n] = {
            "general_ms": round(g * 1e3, 3),
            "expanded_ms": round(e * 1e3, 3),
            "host_ms": round(host_per_sig * n * 1e3, 3),
        }
        print(f"ed25519 n={n}: general {g * 1e3:.2f} ms, expanded "
              f"{e * 1e3:.2f} ms, host {host_per_sig * n * 1e3:.2f} ms",
              flush=True)

    # Kernel-shape A/B: windows per fori_loop iteration (69 = 3 x 23).
    # Unrolling trades program size for cross-window ILP; measure at
    # the headline batch.
    n_ab = 10240 if 10240 in SIZES and not cpu else max(
        s for s in SIZES if s <= 1024)
    idx_ab = list(range(n_ab))
    ab_res = {}
    wpi_default = ex.WINDOWS_PER_ITER
    for wpi in (1, 3, 23):
        ex.WINDOWS_PER_ITER = wpi
        try:
            exp.verify(idx_ab, msgs[:n_ab], sigs[:n_ab])  # compile
            t = p50(lambda: exp.verify(idx_ab, msgs[:n_ab], sigs[:n_ab]),
                    reps=3)
            ab_res[wpi] = round(t * 1e3, 3)
            print(f"expanded wpi={wpi} @ {n_ab}: {t * 1e3:.2f} ms",
                  flush=True)
        finally:
            ex.WINDOWS_PER_ITER = wpi_default
    results["ed25519"]["windows_per_iter_ms"] = ab_res

    # sr25519
    from tendermint_tpu.crypto import sr25519_ref as sr
    from tendermint_tpu.crypto.tpu.sr_verify import verify_batch_sr

    n_sr = max(SR_SIZES)
    minis = [hashlib.sha256(b"sr%d" % i).digest() for i in range(n_sr)]
    spubs = [sr.public_key_from_mini(m) for m in minis]
    smsgs = [b"sr vote %d" % i for i in range(n_sr)]
    ssigs = [sr.sign(m, msg) for m, msg in zip(minis, smsgs)]
    t0 = time.perf_counter()
    for i in range(8):
        sr.verify(spubs[i], smsgs[i], ssigs[i])
    sr_host = (time.perf_counter() - t0) / 8
    results["sr25519"]["host_ms_per_sig"] = round(sr_host * 1e3, 2)
    for n in SR_SIZES:
        verify_batch_sr(spubs[:n], smsgs[:n], ssigs[:n])  # compile
        d = p50(lambda: verify_batch_sr(spubs[:n], smsgs[:n], ssigs[:n]),
                reps=3)
        results["sr25519"][n] = {
            "device_ms": round(d * 1e3, 3),
            "host_ms": round(sr_host * n * 1e3, 1),
        }
        print(f"sr25519 n={n}: device {d * 1e3:.1f} ms vs host "
              f"{sr_host * n * 1e3:.0f} ms", flush=True)

    # derive recommendations
    def crossover(kind):
        for n in SIZES:
            r = results["ed25519"][n]
            if r[kind] < r["host_ms"]:
                return n
        return None

    dev_thresh = crossover("general_ms")
    exp_wins = None
    for n in SIZES:
        r = results["ed25519"][n]
        if r["expanded_ms"] < r["general_ms"] and \
                r["expanded_ms"] < r["host_ms"]:
            exp_wins = n
            break
    # the device-launch floor bounds a useful micro-batch window
    launch_ms = min(results["ed25519"][SIZES[0]]["general_ms"],
                    results["ed25519"][SIZES[0]]["expanded_ms"])
    results["recommend"] = {
        "_DEVICE_THRESHOLD": dev_thresh,
        "_EXPAND_MIN": exp_wins,
        "device_launch_floor_ms": launch_ms,
        "vote_batch_window_ms_>=": round(min(launch_ms, 50.0), 1),
    }
    print("recommend:", results["recommend"], flush=True)

    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        f.write("# Measured batching thresholds\n\n")
        f.write(f"Device: `{device}`"
                + (" (CPU-forced smoke run — NOT tuning data)\n\n"
                   if cpu else "\n\n"))
        f.write(f"Host ed25519 strict verify: "
                f"{results['ed25519']['host_us_per_sig']} µs/sig; "
                f"host sr25519: {results['sr25519']['host_ms_per_sig']}"
                " ms/sig.\n\n")
        f.write("| batch | host (ms) | general kernel (ms) | "
                "expanded kernel (ms) |\n|---|---|---|---|\n")
        for n in SIZES:
            r = results["ed25519"][n]
            f.write(f"| {n} | {r['host_ms']} | {r['general_ms']} | "
                    f"{r['expanded_ms']} |\n")
        f.write("\n| sr25519 batch | host (ms) | device (ms) |\n"
                "|---|---|---|\n")
        for n in SR_SIZES:
            r = results["sr25519"][n]
            f.write(f"| {n} | {r['host_ms']} | {r['device_ms']} |\n")
        f.write(f"\nRecommendations: `{json.dumps(results['recommend'])}`\n")
        f.write("\nRaw JSON:\n\n```json\n"
                + json.dumps(results, indent=1) + "\n```\n")
    print(f"wrote {out_path}")


if __name__ == "__main__":
    main()
