"""Batched sr25519 (schnorrkel) verification: Merlin on host (SIMD,
crypto/merlin_batch.py), the group equation on device.

Per lane, schnorrkel verify accepts iff
    encode([s]B - [k]A) == R_bytes
with k the Merlin transcript challenge (host) and encode the ristretto
encoding. Over the quotient group that is ristretto-EQUALITY of
V = [s]B + [k](-A) and decode(R_bytes), so the kernel never encodes:
decode A and R (ristretto.py), then one fused 64-window loop — [k](-A)
via per-lane 4-bit Straus windows, [s]B via the shared fixed-base comb
(the SAME btab the ed25519 kernel uses; windows 64..68 of its 69 are
identity rows and are simply not iterated here, k and s both < L <
2^253 = 64 nibbles).

The jitted program is `sr25519_kernel` (`jit_sr25519_kernel` to the
profiler: a name the general ed25519 program does not share) and names
its phases with `jax.named_scope`, as the ed25519 programs do (PHASES).
The host's Merlin transcripts are the span `crypto.sr_merlin` inside
the launch's pack stage.

Semantics match sr25519_ref.verify bit-for-bit (tested on schnorrkel-
anchored keys, torsioned/corrupted lanes, non-canonical encodings).
Reference surface: crypto/sr25519/pubkey.go:34-61 (BASELINE config #4:
mixed ed25519+sr25519 evidence batches).
"""

from __future__ import annotations

import functools

import numpy as np

from ...libs import tracing
from .. import ed25519_ref as ref
from . import ledger as _ledger
from . import verify as tv

_L = ref.L
_P = ref.P
_WINDOWS = 64  # k, s < L < 2^253: 64 nibbles each

# The kernel's phases, in order (jax.named_scope): both ristretto
# decodes, the 16-entry window table of -A, the fused 64-window loop
# with its last add, the ristretto equality with the verdict.
PHASES = ("sr25519.decode", "sr25519.table", "sr25519.msm",
          "sr25519.compare")

_P_WORDS = np.frombuffer(_P.to_bytes(32, "little"), np.uint64)
_L_WORDS = np.frombuffer(_L.to_bytes(32, "little"), np.uint64)


def _lt_words(vals: np.ndarray, bound_words: np.ndarray) -> np.ndarray:
    """(N, 32) uint8 little-endian < bound, vectorized per 64-bit word."""
    words = vals.copy().view(np.uint64)  # (N, 4)
    lt = np.zeros(len(vals), bool)
    gt = np.zeros(len(vals), bool)
    for w in (3, 2, 1, 0):
        lt |= ~gt & ~lt & (words[:, w] < bound_words[w])
        gt |= ~gt & ~lt & (words[:, w] > bound_words[w])
    return lt


@functools.cache
def _kernel():
    import jax
    import jax.numpy as jnp

    from . import edwards as ed
    from . import ristretto as rs
    from .fieldsel import F as fe

    # the field operations as calls: a shape's first launch is ~5 s of
    # tracing and lowering for ~35, and the program runs as fast or
    # faster at every bucket measured (fe.as_calls)
    @jax.jit
    @fe.as_calls()
    def sr25519_kernel(ab, rb, kdig, sdig, a_pre, r_pre, s_ok, btab):
        n = ab.shape[0]
        with jax.named_scope("sr25519.decode"):
            a_limbs = fe.limbs_from_bytes(ab.astype(jnp.int32).T)
            r_limbs = fe.limbs_from_bytes(rb.astype(jnp.int32).T)
            # Fused 2N ristretto decode (one sqrt-ratio dispatch, like
            # the ed25519 kernel's fused A/R decompression).
            limbs2 = jnp.concatenate([a_limbs, r_limbs], axis=1)
            pre2 = jnp.concatenate(
                [jnp.asarray(a_pre), jnp.asarray(r_pre)])
            p2, ok2 = rs.decode(limbs2, pre2)
            A = ed.Point(p2.x[:, :n], p2.y[:, :n], p2.z[:, :n],
                         p2.t[:, :n])
            R = ed.Point(p2.x[:, n:], p2.y[:, n:], p2.z[:, n:],
                         p2.t[:, n:])
            a_ok, r_ok = ok2[:n], ok2[n:]

        with jax.named_scope("sr25519.table"):
            neg_a = ed.neg(A)
            tbl = ed.build_window_table(neg_a, 16)

        def body(w, accs):
            acc_a, acc_b = accs
            # [k](-A): MSB-first windows with 4 doublings between.
            acc_a = ed.double(ed.double(ed.double(ed.double(acc_a))))
            dk = jax.lax.dynamic_index_in_dim(
                kdig, _WINDOWS - 1 - w, 0, keepdims=False)
            acc_a = ed.add(acc_a, ed.select(tbl, dk))
            # [s]B: LSB-first comb over the shared base tables.
            ds = jax.lax.dynamic_index_in_dim(sdig, w, 0, keepdims=False)
            bw = jax.lax.dynamic_index_in_dim(btab, w, 0, keepdims=False)
            qx, qy, qt = ed.select_const(bw, ds)
            acc_b = ed.add_z1(acc_b, qx, qy, qt)
            return (acc_a, acc_b)

        with jax.named_scope("sr25519.msm"):
            acc_a, acc_b = jax.lax.fori_loop(
                0, _WINDOWS, body, (ed.identity(n), ed.identity(n))
            )
            v = ed.add(acc_a, acc_b)
        with jax.named_scope("sr25519.compare"):
            return rs.equal(v, R) & a_ok & r_ok & jnp.asarray(s_ok)

    return sr25519_kernel


def _nibbles(ints, n: int) -> np.ndarray:
    """(N,) python ints < 2^256 -> (64, N) int32 nibbles LSB-first."""
    raw = np.frombuffer(
        b"".join(int(v).to_bytes(32, "little") for v in ints), np.uint8
    ).reshape(n, 32)
    out = np.empty((64, n), np.int32)
    out[0::2] = (raw & 0x0F).T
    out[1::2] = (raw >> 4).T
    return out


def verify_batch_sr(pubs, msgs, sigs, ctx: bytes = b"",
                    *, cpu: bool = False) -> np.ndarray:
    """Batched schnorrkel verify on the default JAX device.

    Returns per-lane verdicts (N,) bool; semantics identical to
    sr25519_ref.verify (marker bit required, canonical s < L,
    ristretto-canonical A and R encodings).

    cpu=True pins the SAME kernel to the XLA CPU backend (native host
    code, no accelerator traffic): the device-outage degradation path
    for sr25519-heavy chains, where the pure-Python oracle's ~5.5
    ms/sig would stall a 10k commit for a minute (VERDICT r4 ask #7).
    Sharding is bypassed — the accelerator mesh is exactly what's
    presumed dead.
    """
    from ..merlin_batch import sr25519_challenges

    n = len(pubs)
    assert len(msgs) == n and len(sigs) == n
    if n == 0:
        return np.zeros(0, bool)

    with _ledger.launch("sr25519_cpu" if cpu else "sr25519") as rec:
        rec.lanes = n
        with rec.stage("pack"):
            well_formed = np.fromiter(
                ((len(p) == 32 and len(s) == 64 and (s[63] & 0x80) != 0)
                 for p, s in zip(pubs, sigs)),
                bool, count=n)
            safe_sigs = [
                s if ok else b"\0" * 63 + b"\x80"
                for s, ok in zip(sigs, well_formed)
            ]
            safe_pubs = [p if ok else b"\0" * 32
                         for p, ok in zip(pubs, well_formed)]

            a_raw = np.frombuffer(
                b"".join(safe_pubs), np.uint8).reshape(n, 32)
            sig_raw = np.frombuffer(
                b"".join(safe_sigs), np.uint8).reshape(n, 64)
            r_raw = np.ascontiguousarray(sig_raw[:, :32])
            s_raw = np.ascontiguousarray(sig_raw[:, 32:])
            s_raw[:, 31] &= 0x7F  # strip schnorrkel marker bit

            # Host preconditions: s < L; A/R canonical (< p) and
            # non-negative.
            s_ok = _lt_words(s_raw, _L_WORDS)
            a_pre = _lt_words(a_raw, _P_WORDS) & ((a_raw[:, 0] & 1) == 0)
            r_pre = _lt_words(r_raw, _P_WORDS) & ((r_raw[:, 0] & 1) == 0)

            # Merlin challenges (SIMD host; transcript sees the WIRE
            # bytes of pk and R, marker included on neither — R is
            # sig[:32] as-is).
            with tracing.TRACER.span(
                    tracing.CRYPTO_SR_MERLIN, lanes=n,
                    groups=len(set(map(len, msgs)))):
                ks = sr25519_challenges(a_raw, list(msgs), r_raw, ctx)
            kdig = _nibbles(ks, n)
            s_ints = [int.from_bytes(s_raw[i].tobytes(), "little")
                      for i in range(n)]
            sdig = _nibbles(s_ints, n)

            # Bucket like the ed25519 path: powers of two up to 1024,
            # then multiples of 1024 (a 10,240-lane batch pads 0%
            # instead of 60%).
            if n <= 1024:
                bucket = tv._MIN_BATCH
                while bucket < n:
                    bucket <<= 1
            else:
                bucket = (n + 1023) // 1024 * 1024
            mesh = None if cpu else tv._mesh()
            shard = mesh is not None and bucket >= tv._SHARD_MIN
            if shard:
                # Odd buckets pad up to a device multiple (inert zero
                # lanes) instead of forfeiting the mesh — same contract
                # as the ed25519 paths (verify.mesh_lane_pad).
                bucket = tv.mesh_lane_pad(bucket, mesh)
            pad = bucket - n
            if pad:
                a_raw = np.pad(a_raw, ((0, pad), (0, 0)))
                r_raw = np.pad(r_raw, ((0, pad), (0, 0)))
                kdig = np.pad(kdig, ((0, 0), (0, pad)))
                sdig = np.pad(sdig, ((0, 0), (0, pad)))
                s_ok = np.pad(s_ok, (0, pad))
                a_pre = np.pad(a_pre, (0, pad))
                r_pre = np.pad(r_pre, (0, pad))

            btab = tv.b_comb_tables()[:_WINDOWS]
            args = dict(ab=a_raw, rb=r_raw, kdig=kdig, sdig=sdig,
                        a_pre=a_pre, r_pre=r_pre, s_ok=s_ok)
        rec.capacity = bucket
        rec.compile_hit = tv.count_compile(
            "sr25519_cpu" if cpu else "sr25519", (bucket, int(cpu)))
        rec.bytes_h2d = _ledger.nbytes_of(args) + int(btab.nbytes)
        with rec.stage("dispatch"):
            if cpu:
                import jax

                with jax.default_device(
                        jax.local_devices(backend="cpu")[0]):
                    out = _kernel()(btab=btab, **args)
            else:
                if shard:
                    import jax

                    row_s, vec_s, repl_s = tv._shardings(mesh)
                    for key, v in args.items():
                        if v.ndim == 1:
                            args[key] = jax.device_put(v, vec_s)
                        elif key in ("kdig", "sdig"):
                            from jax.sharding import (NamedSharding,
                                                      PartitionSpec)

                            args[key] = jax.device_put(
                                v, NamedSharding(
                                    mesh, PartitionSpec(None, "dp")))
                        else:
                            args[key] = jax.device_put(v, row_s)
                    btab = jax.device_put(btab, repl_s)
                    tv.count_shard_lanes(mesh, bucket)
                    d = int(mesh.devices.size)
                    rec.n_devices = d
                    rec.shard_lanes = [bucket // d] * d
                out = _kernel()(btab=btab, **args)
        with rec.stage("exec"):
            getattr(out, "block_until_ready", lambda: None)()
        with rec.stage("readback"):
            full = np.asarray(out)
        rec.result(out)
        rec.bytes_d2h = int(full.nbytes)
        res = full[:n] & well_formed
        rec.verdicts(res)
    return res
