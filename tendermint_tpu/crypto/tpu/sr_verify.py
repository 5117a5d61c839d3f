"""Batched sr25519 (schnorrkel) verification, all of it on device: the
Merlin challenge from bytes, then the group equation.

Per lane, schnorrkel verify accepts iff
    encode([s]B - [k]A) == R_bytes
with k the Merlin transcript challenge and encode the ristretto
encoding. Over the quotient group that is ristretto-EQUALITY of
V = [s]B + [k](-A) and decode(R_bytes), so the kernel never encodes:
decode A and R (ristretto.py), then one fused 69-window loop: [k](-A)
via per-lane 4-bit Straus windows, [s]B via the shared fixed-base comb
(the SAME btab the ed25519 kernel uses; s < 2^256 fills 64 windows, the
last five add the identity).

The challenge never exists on the host. STROBE's op schedule depends on
byte lengths only, so a lane's transcript is one constant state (all of
it up to and including the signing context, computed once a context by
the scalar crypto/merlin.py) plus 2-4 blocks of 168 bytes: "XOR the
block into the rate, permute". The host lays the blocks out with byte
operations (one template a message length: labels, length words, op
headers, pad bytes; three column writes: message, pk, R) under the span
`crypto.sr_merlin` of the launch's pack stage; the kernel's first phase
`sr25519.merlin` runs Keccak-f[1600] over them in uint32 pairs, lanes
minor, and folds the 64 challenge bytes with what the ed25519 programs
use for SHA-512 mod L (scalar.fold_digest: a representative of k mod L
below 2^271; ristretto equality is blind to the multiple of L, as the
cofactored ed25519 equation is). crypto/merlin_batch.py is no longer on
this path: it signs the benchmark's chains and is the tests' oracle.

The jitted program is `sr25519_kernel` (`jit_sr25519_kernel` to the
profiler: a name the general ed25519 program does not share) and names
its phases with `jax.named_scope`, as the ed25519 programs do (PHASES).

Semantics match sr25519_ref.verify bit-for-bit (tested on schnorrkel-
anchored keys, torsioned/corrupted lanes, non-canonical encodings).
Reference surface: crypto/sr25519/pubkey.go:34-61 (BASELINE config #4:
mixed ed25519+sr25519 evidence batches).
"""

from __future__ import annotations

import functools

import numpy as np

from ...libs import jaxcache, tracing
from .. import ed25519_ref as ref
from .. import merlin
from ..merlin_batch import _PI_SRC, _RC, _ROTC_FLAT
from . import ledger as _ledger
from . import scalar as sc
from . import verify as tv

_L = ref.L
_P = ref.P
# k folded below 2^271 (scalar.fold_digest): the general ed25519
# kernel's window count, over the whole of its comb tables
_WINDOWS = sc.DIGITS_K

# The kernel's phases, in order (jax.named_scope): the Merlin challenge
# (Keccak-f[1600] over the lane's blocks, the fold mod L, both scalars'
# digits), both ristretto decodes, the 16-entry window table of -A, the
# fused 69-window loop with its last add, the ristretto equality with
# the verdict.
PHASES = ("sr25519.merlin", "sr25519.decode", "sr25519.table",
          "sr25519.msm", "sr25519.compare")

_P_WORDS = np.frombuffer(_P.to_bytes(32, "little"), np.uint64)
_L_WORDS = np.frombuffer(_L.to_bytes(32, "little"), np.uint64)

# STROBE-128 absorbs 166 bytes a permutation and pads with two more
# (`pos_begin` is XORed wherever the data ends, 0x04 after it, 0x80 at
# byte 167): one block is 21 of Keccak's 25 words of 64 bits.
_BLOCK = merlin.Strobe128.R + 2
_BLOCK_LANES = _BLOCK // 8
_BLOCK_WORDS = 2 * _BLOCK_LANES
# The block dimension of a launch: under the empty context the node
# signs with, every message up to 460 bytes (a vote's or a proposal's
# sign-bytes are under 294) is 4 blocks or fewer, so no message mix a
# node can form compiles a second shape.
_MIN_BLOCKS = 4


def _lt_words(vals: np.ndarray, bound_words: np.ndarray) -> np.ndarray:
    """(N, 32) uint8 little-endian < bound, vectorized per 64-bit word."""
    words = vals.copy().view(np.uint64)  # (N, 4)
    lt = np.zeros(len(vals), bool)
    gt = np.zeros(len(vals), bool)
    for w in (3, 2, 1, 0):
        lt |= ~gt & ~lt & (words[:, w] < bound_words[w])
        gt |= ~gt & ~lt & (words[:, w] > bound_words[w])
    return lt


# --- host: a transcript as blocks --------------------------------------


class _BlockRecorder(merlin.Strobe128):
    """The scalar STROBE run on lengths alone: it keeps what each
    permutation would have XORed into the rate, block by block, and
    where every `ad`'s bytes landed, and permutes nothing. The op
    schedule is crypto/merlin.py's own (`_begin_op`, `_absorb`), so the
    blocks are the oracle's by construction."""

    def __init__(self, at: merlin.Strobe128):
        self.state = bytearray(200)
        self.pos, self.pos_begin = at.pos, at.pos_begin
        self.cur_flags = at.cur_flags
        self.blocks: list[bytes] = []
        self.fields: list[list[tuple[int, int, int]]] = []

    def _run_f(self) -> None:
        self.state[self.pos] ^= self.pos_begin
        self.state[self.pos + 1] ^= 0x04
        self.state[self.R + 1] ^= 0x80
        self.blocks.append(bytes(self.state[:_BLOCK]))
        self.state = bytearray(200)
        self.pos = 0
        self.pos_begin = 0

    def ad(self, data: bytes, more: bool) -> None:
        self._begin_op(self.FLAG_A, more)
        segs = []  # (offset among the lane's blocks, offset in data, bytes)
        i = 0
        while i < len(data):
            take = min(self.R - self.pos, len(data) - i)
            segs.append((len(self.blocks) * _BLOCK + self.pos, i, take))
            self._absorb(data[i:i + take])
            i += take
        self.fields.append(segs)


@functools.lru_cache(maxsize=16)
def _prefix(ctx: bytes) -> tuple[merlin.Strobe128, np.ndarray]:
    """The lane-independent head of every verify transcript (protocol
    label, "SigningContext", the context): its STROBE, never touched
    again, and its state as the kernel takes it, (25, 2) uint32 (lo,
    hi)."""
    t = merlin.Transcript(b"SigningContext")
    t.append_message(b"", ctx)
    s0 = np.frombuffer(bytes(t._strobe.state), np.uint32).reshape(25, 2)
    return t._strobe, s0


@functools.lru_cache(maxsize=1024)
def _layout(ctx: bytes, mlen: int):
    """What follows the context for a message of `mlen` bytes: the
    blocks with every byte that is a function of the lengths in place
    and zeros where the message, pk and R go, as one uint8 row, and
    those three fields' segments."""
    rec = _BlockRecorder(_prefix(ctx)[0])
    t = object.__new__(merlin.Transcript)
    t._strobe = rec
    t.append_message(b"sign-bytes", bytes(mlen))
    t.append_message(b"proto-name", b"Schnorr-sig")
    t.append_message(b"sign:pk", bytes(32))
    t.append_message(b"sign:R", bytes(32))
    # challenge_bytes(b"sign:c", 64) as far as the permutation its PRF
    # forces: the challenge is the first 64 bytes of the state after it
    rec.meta_ad(b"sign:c", False)
    rec.meta_ad((64).to_bytes(4, "little"), True)
    rec._begin_op(rec.FLAG_I | rec.FLAG_A | rec.FLAG_C, False)
    assert rec.pos == 0 and not any(rec.state)
    msg, _proto, pk, r = rec.fields
    template = np.frombuffer(b"".join(rec.blocks), np.uint8)
    template.setflags(write=False)
    return template, (msg, pk, r)


def _challenge_blocks(a_raw: np.ndarray, msgs, r_raw: np.ndarray,
                      ctx: bytes, bucket: int):
    """Every lane's transcript as blocks: ((bucket, B * 42) uint32, the
    per-lane block counts (bucket,) int32 (0 on a pad lane: its state
    stands still), the number of message-length groups). Byte operations
    only: a template and three column writes a group."""
    n = len(msgs)
    lens = np.fromiter(map(len, msgs), np.int64, count=n)
    flat = np.frombuffer(b"".join(msgs), np.uint8)
    starts = np.cumsum(lens) - lens
    order = np.argsort(lens, kind="stable")
    groups = np.split(
        order, np.flatnonzero(np.diff(lens[order])) + 1)
    mlens = [int(lens[idx[0]]) for idx in groups]
    layouts = [_layout(ctx, mlen) for mlen in mlens]
    nblocks = _MIN_BLOCKS
    while nblocks * _BLOCK < max(len(tmpl) for tmpl, _ in layouts):
        nblocks <<= 1
    buf = np.zeros((bucket, nblocks * _BLOCK), np.uint8)
    counts = np.zeros(bucket, np.int32)
    for idx, mlen, (tmpl, fields) in zip(groups, mlens, layouts):
        rows = np.tile(tmpl, (len(idx), 1))
        sources = (flat[starts[idx][:, None] + np.arange(mlen)],
                   a_raw[idx], r_raw[idx])
        for segs, src in zip(fields, sources):
            for dst, at, take in segs:
                rows[:, dst:dst + take] = src[:, at:at + take]
        buf[idx, :len(tmpl)] = rows
        counts[idx] = len(tmpl) // _BLOCK
    return buf.view(np.uint32), counts, len(groups)


# --- device -------------------------------------------------------------


@functools.cache
def _keccak_consts():
    """Keccak-f[1600] over (lo, hi) uint32 pairs. rho and pi as one
    pick among the state's 50 words: destination lane i takes lane
    `_PI_SRC[i]` rotated left by r; a rotation of 32 or more swaps the
    halves first, which the pick does, and leaves r % 32 to shift."""
    rot = _ROTC_FLAT[_PI_SRC].astype(np.int64)
    swap = (rot >= 32).astype(np.int64)
    lo_from = tuple(int(v) for v in 2 * _PI_SRC + swap)
    hi_from = tuple(int(v) for v in 2 * _PI_SRC + 1 - swap)
    shift = (rot % 32).astype(np.uint32).reshape(25, 1)
    # iota: the round constant into lane 0, as a whole-state XOR
    rc = np.zeros((24, 25, 2, 1), np.uint32)
    rc[:, 0, 0, 0] = _RC & np.uint64(0xFFFFFFFF)
    rc[:, 0, 1, 0] = _RC >> np.uint64(32)
    return lo_from, hi_from, shift, rc


def keccak_f1600(a):
    """(25, 2, N) uint32, lane x + 5y as (lo, hi), lanes of the batch
    minor -> the same after the 24 rounds. One `lax.fori_loop` whose
    body is a few dozen equations whatever N."""
    import jax
    import jax.numpy as jnp

    lo_from, hi_from, shift, rc = _keccak_consts()
    n = a.shape[-1]
    shift = jnp.asarray(shift)
    rc = jnp.asarray(rc)

    def rotl(lo, hi, r):
        # r in 0..31, per row; (x >> (31 - r)) >> 1 is x >> (32 - r)
        # without the shift by 32 that r = 0 would ask for
        return ((lo << r) | ((hi >> (31 - r)) >> 1),
                (hi << r) | ((lo >> (31 - r)) >> 1))

    def one_round(i, a):
        a = a.reshape(5, 5, 2, n)  # [y, x]
        c = a[0] ^ a[1] ^ a[2] ^ a[3] ^ a[4]
        c1 = jnp.roll(c, -1, axis=0)
        d = jnp.roll(c, 1, axis=0) ^ jnp.stack(
            rotl(c1[:, 0], c1[:, 1], 1), axis=1)
        a = (a ^ d).reshape(50, n)
        lo = jnp.stack([a[j] for j in lo_from])
        hi = jnp.stack([a[j] for j in hi_from])
        b = jnp.stack(rotl(lo, hi, shift), axis=1).reshape(5, 5, 2, n)
        a = b ^ (~jnp.roll(b, -1, axis=1) & jnp.roll(b, -2, axis=1))
        return a.reshape(25, 2, n) ^ jax.lax.dynamic_index_in_dim(
            rc, i, 0, keepdims=False)

    return jax.lax.fori_loop(0, 24, one_round, a)


def challenge_digest(blocks, counts, s0):
    """blocks (N, B * 42) uint32, counts (N,), s0 (25, 2) uint32 ->
    (64, N) int32: each lane's 64 challenge bytes, little-endian. A lane
    absorbs its first `counts` blocks and keeps its state past them."""
    import jax
    import jax.numpy as jnp

    n = blocks.shape[0]
    nblocks = blocks.shape[1] // _BLOCK_WORDS
    words = blocks.T.reshape(nblocks, _BLOCK_LANES, 2, n)
    # the capacity's four lanes take no input
    words = jnp.pad(words, ((0, 0), (0, 25 - _BLOCK_LANES), (0, 0), (0, 0)))

    def absorb(b, state):
        block = jax.lax.dynamic_index_in_dim(words, b, 0, keepdims=False)
        return jnp.where(b < counts, keccak_f1600(state ^ block), state)

    state = jax.lax.fori_loop(
        0, nblocks, absorb,
        jnp.broadcast_to(jnp.asarray(s0)[:, :, None], (25, 2, n)))
    out = state[:8].reshape(16, 1, n) >> jnp.arange(
        0, 32, 8, dtype=jnp.uint32).reshape(1, 4, 1)
    return (out & 0xFF).reshape(64, n).astype(jnp.int32)


@jaxcache.one_program
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
    def sr25519_kernel(ab, rb, blocks, counts, s0, sb, a_pre, r_pre,
                       s_ok, btab):
        n = ab.shape[0]
        with jax.named_scope("sr25519.merlin"):
            # (69, N) MSB-first, of a representative of k mod L
            kdig = sc.fold_digest(challenge_digest(blocks, counts, s0))
            # s: nibbles LSB-first, no digit in the last five windows
            s_rows = sb.astype(jnp.int32).T
            sdig = jnp.pad(
                jnp.stack([s_rows & 15, s_rows >> 4], axis=1).reshape(64, n),
                ((0, _WINDOWS - 64), (0, 0)))
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
            dk = jax.lax.dynamic_index_in_dim(kdig, w, 0, keepdims=False)
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

            # The Merlin transcripts as blocks (the transcript sees the
            # WIRE bytes of pk and R, marker included on neither — R is
            # sig[:32] as-is); the device derives the challenges.
            with tracing.TRACER.span(
                    tracing.CRYPTO_SR_MERLIN, lanes=n) as span:
                blocks, counts, groups = _challenge_blocks(
                    a_raw, msgs, r_raw, ctx, bucket)
                nblocks = blocks.shape[1] // _BLOCK_WORDS
                span.set_attr("groups", groups)
                span.set_attr("blocks", nblocks)

            pad = bucket - n
            if pad:
                a_raw = np.pad(a_raw, ((0, pad), (0, 0)))
                r_raw = np.pad(r_raw, ((0, pad), (0, 0)))
                s_raw = np.pad(s_raw, ((0, pad), (0, 0)))
                s_ok = np.pad(s_ok, (0, pad))
                a_pre = np.pad(a_pre, (0, pad))
                r_pre = np.pad(r_pre, (0, pad))

            btab = tv.b_comb_tables()
            s0 = _prefix(ctx)[1]
            args = dict(ab=a_raw, rb=r_raw, blocks=blocks, counts=counts,
                        sb=s_raw, a_pre=a_pre, r_pre=r_pre, s_ok=s_ok)
        rec.capacity = bucket
        rec.compile_hit = tv.count_compile(
            "sr25519_cpu" if cpu else "sr25519",
            (bucket, nblocks, int(cpu)))
        rec.bytes_h2d = (_ledger.nbytes_of(args) + int(btab.nbytes)
                         + int(s0.nbytes))
        with rec.stage("dispatch"):
            if cpu:
                import jax

                with jax.default_device(
                        jax.local_devices(backend="cpu")[0]):
                    out = _kernel()(btab=btab, s0=s0, **args)
            else:
                if shard:
                    import jax

                    row_s, vec_s, repl_s = tv._shardings(mesh)
                    for key, v in args.items():
                        args[key] = jax.device_put(
                            v, vec_s if v.ndim == 1 else row_s)
                    btab = jax.device_put(btab, repl_s)
                    s0 = jax.device_put(s0, repl_s)
                    tv.count_shard_lanes(mesh, bucket)
                    d = int(mesh.devices.size)
                    rec.n_devices = d
                    rec.shard_lanes = [bucket // d] * d
                out = _kernel()(btab=btab, s0=s0, **args)
        with rec.stage("exec"):
            getattr(out, "block_until_ready", lambda: None)()
        with rec.stage("readback"):
            full = np.asarray(out)
        rec.result(out)
        rec.bytes_d2h = int(full.nbytes)
        res = full[:n] & well_formed
        rec.verdicts(res)
    return res
