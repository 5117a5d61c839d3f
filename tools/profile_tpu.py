"""Stage-by-stage timing of the expanded-path verify on the real chip.

Prints one line per stage so a hang/timeout points at the guilty stage.
Usage: python tools/profile_tpu.py [n_keys] [n_lanes]
"""

import hashlib
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

T0 = time.perf_counter()


def log(msg):
    print(f"[{time.perf_counter() - T0:8.2f}s] {msg}", flush=True)


def main():
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    n_keys = int(args[0]) if args else 1024
    n_lanes = int(args[1]) if len(args) > 1 else n_keys

    log("importing jax...")
    import jax

    log(f"devices: {jax.devices()}")

    from cryptography.hazmat.primitives import serialization
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
    )

    keys = [
        Ed25519PrivateKey.from_private_bytes(
            hashlib.sha256(b"bench%d" % i).digest())
        for i in range(n_keys)
    ]
    pubs = [
        k.public_key().public_bytes(
            serialization.Encoding.Raw, serialization.PublicFormat.Raw)
        for k in keys
    ]
    msgs = [b"precommit h=1234 r=0 block=deadbeef val=%d" % i
            for i in range(n_lanes)]
    sigs = [keys[i % n_keys].sign(m) for i, m in enumerate(msgs)]
    idx = [i % n_keys for i in range(n_lanes)]
    log(f"made {n_keys} keys / {n_lanes} lanes")

    from tendermint_tpu.crypto.tpu import expanded as ex

    t = time.perf_counter()
    exp = ex.ExpandedKeys(pubs)
    log(f"table build call returned in {time.perf_counter() - t:.2f}s "
        "(async dispatch)")
    t = time.perf_counter()
    exp.tables.block_until_ready()
    log(f"table build synced in {time.perf_counter() - t:.2f}s; "
        f"shape {exp.tables.shape} "
        f"({exp.tables.size * 4 / 2**30:.2f} GiB)")

    rec = {"n_keys": n_keys, "n_lanes": n_lanes,
           "device": str(jax.devices()[0]),
           "windows_per_iter": ex.WINDOWS_PER_ITER}

    t = time.perf_counter()
    out = exp.verify(idx, msgs, sigs)
    log(f"first verify (compile+run) {time.perf_counter() - t:.2f}s; "
        f"all={bool(out.all())}")

    warms = []
    for i in range(3):
        t = time.perf_counter()
        out = exp.verify(idx, msgs, sigs)
        warms.append(time.perf_counter() - t)
        log(f"warm verify #{i} {1e3 * warms[-1]:.1f}ms")
    rec["warm_verify_p50_ms"] = round(1e3 * sorted(warms)[1], 2)

    t = time.perf_counter()
    pidx, packed, _wf, slots = exp._prepare(idx, msgs, sigs)
    in_order = slots is not None
    rec["host_prepare_ms"] = round(1e3 * (time.perf_counter() - t), 2)
    log(f"host prepare {rec['host_prepare_ms']:.1f}ms")
    for i in range(3):
        t = time.perf_counter()
        o = exp._launch(pidx, packed, in_order)
        o.block_until_ready()
        log(f"device launch #{i} {1e3 * (time.perf_counter() - t):.1f}ms")

    # Separate per-launch DEVICE time from the host round trip and
    # per-call input transfer in the synced numbers above: shared
    # two-burst slope estimator (same protocol bench.py reports).
    from tools.bench_util import pipelined_exec_s

    dpidx = jax.device_put(pidx)
    dpacked = {k: jax.device_put(v) for k, v in packed.items()}
    per, single, totals = pipelined_exec_s(
        lambda: exp._launch(dpidx, dpacked, in_order))
    for k, tt in totals.items():
        log(f"pipelined x{k} (device-resident inputs): total "
            f"{1e3 * tt:.1f}ms")
    log(f"single synced launch {1e3 * single:.1f}ms; device exec "
        f"{'unmeasurable (host jitter)' if per is None else f'{1e3 * per:.2f}ms'}/launch")
    rec["single_launch_synced_ms"] = round(1e3 * single, 2)
    rec["device_exec_ms_per_launch"] = (
        round(1e3 * per, 3) if per else None)
    # Same launches from host numpy inputs: includes per-call
    # host->device transfer (the production cold-call shape).
    for k in (1, 4):
        t = time.perf_counter()
        outs = [exp._launch(pidx, packed, in_order)
                for _ in range(k)]
        outs[-1].block_until_ready()
        dt = 1e3 * (time.perf_counter() - t)
        log(f"pipelined x{k} (host inputs): total {dt:.1f}ms "
            f"({dt / k:.1f}ms/launch)")
        rec[f"host_input_pipelined_x{k}_ms_per_launch"] = round(dt / k, 2)


if __name__ == "__main__":
    main()
