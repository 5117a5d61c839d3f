"""Batched ZIP-215 ed25519 verification: one fused device program.

The device program takes RAW BYTES (pubkeys, signatures, SHA-padded
messages) and produces per-lane verdicts; everything in between —
SHA-512 of R||A||M (sha512.py), challenge folding mod L (scalar.py),
byte->limb unpacking, ZIP-215 decompression, and the fused
Straus-window + fixed-base-comb scalar multiplication — runs on device
in one XLA program. Host work is four numpy concatenations and the
S < L range check; round 1's per-signature Python packing loop
(~300 ms at 10k lanes on this single-core host) is gone.

Per lane the kernel checks the cofactored equation
    [8]([S]B - [k](A) - R) == identity
with k folded to a 271-bit representative (see scalar.fold_digest for
why no canonical mod-L reduction is needed): [k](-A) via 4-bit windows
MSB-first over 69 windows (4 doublings + 1 per-lane table add each),
[S]B via a fixed-base comb (shared 16-entry tables of j * 16^w * B),
both inside one lax.fori_loop.

Semantics match crypto/ed25519_ref.py bit-for-bit (golden-tested):
reference hot-path parity per SURVEY §2.2 — the call sites it serves
are VoteSet.AddVote, VerifyCommit/Light/LightTrusting, evidence and
light-client verification (reference: types/vote_set.go:203,
types/validator_set.go:694,753,817, evidence/verify.go:165).
"""

from __future__ import annotations

import functools
import hashlib
import re
from typing import NamedTuple

import numpy as np

from .. import ed25519_ref as ref
from . import ledger as _ledger
from ...libs import tracing

# Warm the native packer at import (node/verifier startup): the
# build-on-first-use cc subprocess must never run lazily inside a
# commit verify — that path has a <5 ms budget.
try:
    from ...native import lib as _native_lib

    _native_lib()
except Exception:  # pragma: no cover - never block import on this
    pass

_L = ref.L
_MAX_BATCH = 1 << 15
_MIN_BATCH = 1 << 7
# Shard over the device mesh only from this bucket size up: tiny
# batches aren't worth the per-device dispatch, and it keeps small-shape
# compiles single-device.
_SHARD_MIN = 1 << 11
_DIGITS_K = 69  # scalar.DIGITS_K; windows in the fused loop

# L as four little-endian uint64 words, for the vectorized S < L check.
_L_WORDS = np.frombuffer(_L.to_bytes(32, "little"), np.uint64)


@functools.cache
def b_comb_tables() -> np.ndarray:
    """(69, 16, 3, NLIMB): affine (x, y, x*y) of j * 16^w * B in the
    active field representation's limb layout/dtype (fieldsel.py).

    Entry (w, 0) is the identity (0, 1, 0). Windows 64..68 exist only
    to keep the fused 69-iteration loop uniform — S has 64 nibbles, the
    padded digit rows select entry 0, so those windows are all-identity.
    Built once host-side with the pure-Python oracle (~1.2k point ops).
    """
    from .fieldsel import F as fe

    tab = np.zeros((_DIGITS_K, 16, 3, fe.NLIMB),
                   np.asarray(fe.to_limbs(0)).dtype)
    base = ref._B_PT
    for w in range(64):
        acc = ref.IDENTITY
        for j in range(16):
            if j == 0:
                x, y = 0, 1
            else:
                acc = ref.pt_add(acc, base)
                x, y = ref.from_extended(acc)
            tab[w, j, 0] = fe.to_limbs(x)
            tab[w, j, 1] = fe.to_limbs(y)
            tab[w, j, 2] = fe.to_limbs((x * y) % ref.P)
        for _ in range(4):
            base = ref.pt_double(base)
    for w in range(64, _DIGITS_K):
        tab[w, :, 1, 0] = 1  # identity (0, 1, 0) in every entry
    tab.setflags(write=False)
    return tab


def _bytes32_to_limbs(arr: np.ndarray) -> np.ndarray:
    """(N, 32) uint8 (top bit already cleared) -> (NLIMB, N) limbs in
    the active field representation (fieldsel.py).

    Host-side helper (tests and table precomputation), implemented in
    pure numpy INDEPENDENTLY of the device unpack (fe.limbs_from_bytes)
    so tests feeding it into kernels cross-check the device path.
    """
    from .fieldsel import F as fe

    bits = np.unpackbits(arr, axis=1, bitorder="little")  # (N, 256)
    width = fe.BITS * fe.NLIMB
    bits = np.pad(bits, ((0, 0), (0, width - 256)))
    bits = bits.reshape(arr.shape[0], fe.NLIMB, fe.BITS)
    weights = (1 << np.arange(fe.BITS, dtype=np.int64))
    limbs = (bits.astype(np.int64) * weights).sum(axis=2)  # (N, NLIMB)
    return np.ascontiguousarray(
        limbs.T.astype(np.asarray(fe.to_limbs(0)).dtype))


def pack_batch(pubs, msgs, sigs, min_blocks: int = 1
               ) -> dict[str, np.ndarray]:
    """Host-side preparation: raw byte arrays + SHA padding + S < L.

    All numpy-vectorized; no per-signature Python.
    """
    n = len(pubs)
    a_raw = np.frombuffer(b"".join(pubs), np.uint8).reshape(n, 32)
    sig_raw = np.frombuffer(b"".join(sigs), np.uint8).reshape(n, 64)
    return pack_arrays(a_raw, sig_raw, msgs, min_blocks)


def pack_arrays(a_raw: np.ndarray, sig_raw: np.ndarray, msgs,
                min_blocks: int = 1) -> dict[str, np.ndarray]:
    """pack_batch core on pre-built (N, 32)/(N, 64) uint8 arrays."""
    return dict(pack_sig_msg(sig_raw, msgs, min_blocks), ab=a_raw)


def pack_sig_msg(sig_raw: np.ndarray, msgs, min_blocks: int = 1
                 ) -> dict[str, np.ndarray]:
    """Signature/message half of the pack — everything except the
    pubkey rows. The expanded-valset path sends only this plus the
    (N,) key indices per launch: its pubkey bytes are already
    device-resident next to the comb tables, so shipping (N, 32)
    pubkey rows per call would be pure wasted host->device transfer
    (32 B/lane — ~330 KB per 10,240-lane commit)."""
    from . import sha512 as sh

    msg_pad, nblocks = sh.pad_messages(list(msgs), prefix_len=64)
    # Bucket the padded width to power-of-two block counts so kernel
    # shapes (and recompiles) stay bounded; extra blocks are zeros and
    # every lane past its own nblocks is frozen in compress_blocks.
    # `min_blocks` (a power of two) is the floor a caller with a closed
    # set of launch shapes gives (LaunchShapes.blocks).
    total_blocks = (msg_pad.shape[1] + 64) // 128
    tb = min_blocks
    while tb < total_blocks:
        tb <<= 1
    if tb != total_blocks:
        msg_pad = np.pad(msg_pad, ((0, 0), (0, (tb - total_blocks) * 128)))

    return dict(
        sb=sig_raw,
        msg=msg_pad,
        nblocks=nblocks,
        s_ok=s_range_ok(sig_raw),
    )


def s_range_ok(sig_raw: np.ndarray) -> np.ndarray:
    """Per-lane S < L check on (N, 64) signature rows (host-side; the
    kernel takes the verdict as an input mask)."""
    n = sig_raw.shape[0]
    s_words = sig_raw[:, 32:].copy().view(np.uint64)  # (n, 4) LE words
    lt = np.zeros(n, bool)
    gt = np.zeros(n, bool)
    for w in (3, 2, 1, 0):
        lt |= ~gt & ~lt & (s_words[:, w] < _L_WORDS[w])
        gt |= ~gt & ~lt & (s_words[:, w] > _L_WORDS[w])
    return lt


# Phase names of the verify programs: jax.named_scope labels that
# survive any edit to a kernel (XLA's own fusion.N numbering does not),
# one vocabulary for the general, expanded and structured programs.
# phase_of_instructions() maps a compiled program's instructions onto
# them.
PHASE_ASSEMBLE = "ed25519.assemble"      # sign bytes built on device
PHASE_GATHER = "ed25519.gather"          # key bytes + comb-table rows
PHASE_SHA512 = "ed25519.sha512"          # hash, fold, digit recode
PHASE_DECOMPRESS = "ed25519.decompress"  # point decompression
PHASE_MSM = "ed25519.msm"                # the windowed double-scalar mul
PHASE_COMPARE = "ed25519.compare"        # identity check + lane flags
PHASES = (PHASE_ASSEMBLE, PHASE_GATHER, PHASE_SHA512, PHASE_DECOMPRESS,
          PHASE_MSM, PHASE_COMPARE)

_HLO_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = [^\n]*?metadata=\{op_name="([^"]*)"',
    re.M)
_PHASE_IN_OP_NAME = re.compile(r"(?<=/)ed25519\.\w+")


def phase_of_instructions(hlo_text: str) -> dict[str, str]:
    """{instruction: phase} of a compiled verify program, from its
    optimized HLO (`compiled.as_text()`): each instruction whose
    `op_name` metadata passes through a PHASES scope, under the
    innermost one. A fusion carries the op_name of its root, so it
    counts whole under that phase; what the compiler added on its own
    (copies, bitcasts) has no op_name and is left out. A profiler trace
    names device operations by these instruction names and holds no
    op_name of its own, which is why the map comes from here."""
    out = {}
    for name, op_name in _HLO_INSTRUCTION.findall(hlo_text):
        phases = _PHASE_IN_OP_NAME.findall(op_name)
        if phases:
            out[name] = phases[-1]
    return out


@functools.cache
def general_core():
    """The general-kernel verify body as a traceable function of
    (ab, sb, msg, nblocks, s_ok, btab) — per-lane pubkey BYTES, fully
    assembled messages ((N, W) padded bytes, or the words the
    structured assembly forms: sha512.challenge_words takes either).
    Shared by the jitted `_kernel` here and
    by crypto/tpu/resident.py's arena kernel (device-resident buffers
    + on-device structured message assembly in front of this exact
    body, so both paths verify bit-identically)."""
    import jax
    import jax.numpy as jnp

    from . import edwards as ed
    from . import scalar as sc
    from . import sha512 as sh
    from .fieldsel import F as fe

    def kernel(ab, sb, msg, nblocks, s_ok, btab):
        # The phase scopes follow the order the operations are traced
        # in (msm opens twice): moving one would change the program and
        # with it every cached executable.
        n = ab.shape[0]
        with jax.named_scope(PHASE_SHA512):
            # --- SHA-512 of R || A || M, all lanes at once.
            digest = sh.compress_blocks(
                sh.challenge_words(sb[:, :32], ab, msg), nblocks)
            digk = sc.fold_digest(
                sh.digest_bytes_le(digest))  # (69, N) MSB-first
        with jax.named_scope(PHASE_DECOMPRESS):
            # --- byte rows.
            a_bytes = ab.astype(jnp.int32).T  # (32, N)
            sig_bytes = sb.astype(jnp.int32).T  # (64, N)
        with jax.named_scope(PHASE_MSM):
            digs = sc.bytes_to_nibbles(sig_bytes[32:])  # (64, N) LSB-first
            digs = jnp.concatenate(
                [digs, jnp.zeros((_DIGITS_K - 64, n), jnp.int32)], axis=0
            )
        with jax.named_scope(PHASE_DECOMPRESS):
            a_sign = a_bytes[31] >> 7
            r_sign = sig_bytes[31] >> 7
            a_top = (a_bytes[31] & 0x7F)[None]
            r_top = (sig_bytes[31] & 0x7F)[None]
            a_y = fe.limbs_from_bytes(
                jnp.concatenate([a_bytes[:31], a_top]))
            r_y = fe.limbs_from_bytes(
                jnp.concatenate([sig_bytes[:31], r_top]))

            # --- decompress A and R fused at width 2N (halves the
            # number of expensive sqrt-exponentiation op dispatches).
            y2 = jnp.concatenate([a_y, r_y], axis=1)
            s2 = jnp.concatenate([a_sign, r_sign])
            p2, ok2 = ed.decompress(y2, s2)
            A = ed.Point(p2.x[:, :n], p2.y[:, :n], p2.z[:, :n], p2.t[:, :n])
            R = ed.Point(p2.x[:, n:], p2.y[:, n:], p2.z[:, n:], p2.t[:, n:])
            a_ok, r_ok = ok2[:n], ok2[n:]

        def body(w, accs):
            acc_a, acc_b = accs
            acc_a = ed.double(ed.double(ed.double(ed.double(acc_a))))
            dk = jax.lax.dynamic_index_in_dim(digk, w, 0, keepdims=False)
            acc_a = ed.add(acc_a, ed.select(tbl, dk))
            ds = jax.lax.dynamic_index_in_dim(digs, w, 0, keepdims=False)
            bw = jax.lax.dynamic_index_in_dim(btab, w, 0, keepdims=False)
            qx, qy, qt = ed.select_const(bw, ds)
            acc_b = ed.add_z1(acc_b, qx, qy, qt)
            return (acc_a, acc_b)

        with jax.named_scope(PHASE_MSM):
            neg_a = ed.neg(A)
            tbl = ed.build_window_table(neg_a, 16)  # (16, 4, 22, N)
            neg_r = ed.neg(R)
            acc_a, acc_b = jax.lax.fori_loop(
                0, _DIGITS_K, body, (ed.identity(n), ed.identity(n))
            )
            v = ed.add(acc_a, acc_b)
            v = ed.add(v, neg_r)
            v = ed.double(ed.double(ed.double(v)))
        with jax.named_scope(PHASE_COMPARE):
            return ed.is_identity(v) & a_ok & r_ok & jnp.asarray(s_ok)

    return kernel


@functools.cache
def _kernel():
    """Build the jitted device kernel lazily (imports jax on first use)."""
    import jax

    core = general_core()

    @jax.jit
    def kernel(ab, sb, msg, nblocks, s_ok, btab):
        return core(ab, sb, msg, nblocks, s_ok, btab)

    return kernel


@functools.cache
def _mesh():
    """A ('dp',) mesh over all local devices, or None single-device.

    The verify workload is pure data-parallel over signature lanes
    (SURVEY §2.10: DP = lanes; the cross-chip axis shards a mega-commit
    over ICI). Every op in the kernel is elementwise over the lane axis
    or a contraction over limb/window axes, so XLA compiles the sharded
    program with zero collectives; the only cross-chip traffic is the
    verdict gather at the end.
    """
    import jax

    devs = jax.devices()
    try:
        from ...libs.metrics import tpu_metrics

        tpu_metrics().mesh_devices.set(max(len(devs), 1))
    except Exception:  # pragma: no cover - metrics never fatal
        pass
    if len(devs) <= 1:
        return None
    import numpy as np_

    from jax.sharding import Mesh

    return Mesh(np_.array(devs), ("dp",))


def _shard_failpoints(mesh) -> None:
    """`device.shard_fail` injection point, evaluated once per mesh
    device per dispatch in deterministic device order (so `nth=K`
    selects the K-th device of the first dispatch). The payload is the
    device string: `error` models a raising chip, `corrupt` models a
    NaN-verdict chip (the payload comes back mangled) — both evict
    ONLY that device; the fabric must reshard and keep serving."""
    from ...libs import failpoints

    if not failpoints.any_armed():
        return
    from .. import batch as cbatch

    for d in mesh.devices.flat:
        name = str(d)
        payload = name.encode()
        try:
            back = failpoints.hit("device.shard_fail", payload)
        except failpoints.FailpointError:
            cbatch.mark_device_failed("ed25519", device=name,
                                      reason="failpoint")
            continue
        if back is not None and bytes(back) != payload:
            cbatch.mark_device_failed("ed25519", device=name,
                                      reason="failpoint")


# degraded meshes keyed by the evicted-device tuple; tiny (bounded by
# the distinct eviction sets a process actually sees)
_DEGRADED_MESHES: dict[tuple, object] = {}


def effective_mesh(probe: bool = True):
    """The mesh the NEXT launch should ride: the full ('dp',) mesh
    minus the devices currently evicted by per-device breakers
    (crypto/batch.py). probe=True (dispatch entry) also runs any due
    half-open per-device probes, so a passing probe re-admits its chip
    and this very call returns the restored full-width mesh. Returns
    None when no multi-device mesh survives (<=1 device: the
    single-device path needs no mesh)."""
    base = _mesh()
    if base is None:
        return None
    _shard_failpoints(base)
    from .. import batch as cbatch

    evicted = tuple(cbatch.evicted_devices("ed25519", probe=probe))
    if not evicted:
        return base
    gone = set(evicted)
    devs = [d for d in base.devices.flat if str(d) not in gone]
    if len(devs) < 2:
        return None
    m = _DEGRADED_MESHES.get(evicted)
    if m is None:
        import numpy as np_

        from jax.sharding import Mesh

        m = _DEGRADED_MESHES[evicted] = Mesh(np_.array(devs), ("dp",))
    return m


def mesh_lane_pad(bucket: int, mesh) -> int:
    """Round a lane bucket up to the next device multiple so an odd
    bucket rides the mesh on padded lanes instead of forfeiting it
    (pre-mesh-fabric behavior: any `bucket % devices != 0` silently
    fell back to a single device)."""
    d = int(mesh.devices.size)
    return -(-bucket // d) * d


def count_shard_lanes(mesh, bucket: int) -> None:
    """tpu_shard_lanes_total{device}: lanes (padding included — the
    device executes them either way) dispatched per mesh device by an
    evenly lane-sharded launch."""
    try:
        from ...libs.metrics import tpu_metrics

        tmet = tpu_metrics()
        d = int(mesh.devices.size)
        per = bucket // d
        for i in range(d):
            tmet.shard_lanes.inc(per, device=str(i))
    except Exception:  # pragma: no cover - metrics never fatal
        pass


def _shardings(mesh):
    """(lane-sharded 2d rows, lane-sharded 1d, replicated) NamedShardings."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return (
        NamedSharding(mesh, P("dp")),      # (N, ...) arrays: shard axis 0
        NamedSharding(mesh, P("dp")),      # (N,) vectors
        NamedSharding(mesh, P()),          # replicated consts
    )


@functools.cache
def _dummy_triple() -> tuple[bytes, bytes, bytes]:
    """A fixed valid (pub, msg, sig) used to pad batches to bucket sizes."""
    seed = hashlib.sha256(b"tendermint_tpu batch pad").digest()
    pub = ref.public_key_from_seed(seed)
    msg = b"pad"
    return (pub, msg, ref.sign(seed, msg))


def _chunks(n: int) -> list[int]:
    """One power-of-two bucket per verify whenever n fits in a bucket.

    Measured on the target device path: a kernel launch costs a fixed
    ~50-100 ms dispatch round trip while padded lanes cost microseconds
    of marginal compute, so splitting a 10,240-sig commit into 8192+2048
    (round 1's policy, tuned for padding waste) doubles latency for
    nothing. Pad up to ONE launch; only batches beyond _MAX_BATCH get
    split, into _MAX_BATCH pieces plus one padded tail."""
    out = []
    while n >= _MAX_BATCH:
        out.append(_MAX_BATCH)
        n -= _MAX_BATCH
    if n:
        up = _MIN_BATCH
        while up < n:
            up <<= 1
        out.append(up)
    return out


class LaunchShapes(NamedTuple):
    """A CLOSED set of shapes for the general program: what a plane
    that may never compile inside its service hands to verify_batch
    and loads at its start (load_general). `lanes` is the ONE lane
    count a launch may have; `blocks` the SHA-512 blocks of
    R || A || message a launch is packed to at least (a power of two;
    2 holds every canonical vote: sign bytes of up to 175 bytes, and a
    50-character chain id makes 166). A batch is cut into pieces of
    `lanes`, the last padded to it. A message past `blocks` still
    verifies, at a shape of its own."""

    lanes: int
    blocks: int = 2

    def fit(self, n: int) -> list[int]:
        return [self.lanes] * -(-n // self.lanes)


def load_general(shapes: LaunchShapes) -> int:
    """Compile (or load from the compile cache) and run once the
    general program at the shape of `shapes`, over lanes that verify
    nothing but the pad triple: what verify_batch(shapes=...) launches
    from then on. Returns the programs loaded (0 for a shape this
    process has launched already)."""
    width = 128 * shapes.blocks - 64
    known = ("general", shapes.lanes, width) in _COMPILED_SHAPES
    dp, dm, ds = _dummy_triple()
    out = _launch_chunk([dp], [dm], [ds], shapes.lanes,
                        min_blocks=shapes.blocks)
    if hasattr(out, "block_until_ready"):
        out.block_until_ready()
    return int(not known)


def verify_batch(pubs, msgs, sigs,
                 shapes: LaunchShapes | None = None) -> np.ndarray:
    """Verify a batch of ed25519 (pub, msg, sig) triples on the default
    JAX device. Returns per-lane verdicts as (N,) bool. ZIP-215 semantics
    identical to ed25519_ref.verify; malformed lengths fail cleanly.
    `shapes`: the caller's closed set of launch shapes (LaunchShapes);
    without it a batch takes _chunks' power-of-two ladder."""
    n = len(pubs)
    assert len(msgs) == n and len(sigs) == n
    if n == 0:
        return np.zeros(0, bool)

    # Pre-screen malformed inputs host-side; keep lanes aligned.
    well_formed = np.fromiter(
        (len(p) == 32 and len(s) == 64 for p, s in zip(pubs, sigs)),
        bool,
        count=n,
    )
    if not well_formed.all():
        dp, dm, ds = _dummy_triple()
        pubs = [p if ok else dp for p, ok in zip(pubs, well_formed)]
        msgs = [m if ok else dm for m, ok in zip(msgs, well_formed)]
        sigs = [s if ok else ds for s, ok in zip(sigs, well_formed)]

    out = np.empty(n, bool)
    start = 0
    pending = []
    from ...libs.metrics import tpu_metrics

    tmet = tpu_metrics()
    sizes = _chunks(n) if shapes is None else shapes.fit(n)
    min_blocks = 1 if shapes is None else shapes.blocks
    tmet.batch_occupancy.observe(n / sum(sizes))
    if len(sizes) > 1:
        tmet.batch_splits.inc()
    t = tracing.TRACER
    with t.span(tracing.CRYPTO_VERIFY, lanes=n, backend="general"):
        for size in sizes:
            end = min(start + size, n)
            rec = _ledger.begin("general")
            rec.lanes = end - start
            try:
                fut = _launch_chunk(pubs[start:end], msgs[start:end],
                                    sigs[start:end], size, rec=rec,
                                    min_blocks=min_blocks)
            except Exception as exc:
                rec.fail(exc)
                raise
            pending.append((start, end, fut, rec))
            start = end
        for s, e, fut, rec in pending:
            # device_exec = wait for the async launch's verdicts to be
            # ready on device; readback = the D2H verdict copy. The
            # split is what lets BENCH tell chip time from wire/host.
            try:
                if hasattr(fut, "block_until_ready"):
                    with rec.stage("exec"), \
                            t.span(tracing.CRYPTO_DEVICE_EXEC,
                                   lanes=e - s):
                        fut.block_until_ready()
                with rec.stage("readback"), \
                        t.span(tracing.CRYPTO_READBACK, lanes=e - s):
                    chunk = np.asarray(fut)
                    out[s:e] = chunk[: e - s]
            except Exception as exc:
                rec.fail(exc)
                raise
            rec.result(fut)
            rec.bytes_d2h = int(chunk.nbytes)
            rec.verdicts(out[s:e])
            rec.done()
    return out & well_formed


# (kernel, shape) keys already launched: a first launch at a new shape
# is what actually triggers an XLA trace+compile under @jax.jit, so
# tpu_jit_compiles_total counts THESE — not the once-per-process
# memoized wrapper builds, which would stay flat through a
# shape-churn compile storm.
_COMPILED_SHAPES: dict[tuple, None] = {}


def count_compile(kernel: str, shape: tuple) -> bool:
    """Returns True when this (kernel, shape) was already launched —
    the launch ledger's compile_cache hit/miss field — and counts the
    miss into tpu_jit_compiles_total."""
    key = (kernel,) + shape
    if key in _COMPILED_SHAPES:
        return True
    _COMPILED_SHAPES[key] = None
    from ...libs.metrics import tpu_metrics

    tpu_metrics().jit_compiles.inc(kernel=kernel)
    return False


def _launch_chunk(pubs, msgs, sigs, bucket: int, rec=None,
                  min_blocks: int = 1):
    """Dispatch one bucket-sized kernel launch; returns the device array
    (async — caller materializes). Padding lanes use a fixed valid
    triple so they cannot affect real lanes. `rec` is the caller's
    launch-ledger record; pack/dispatch timing lands on the same
    blocks the spans already bracket."""
    import contextlib

    n = len(pubs)
    t = tracing.TRACER
    mesh = effective_mesh()
    shard = mesh is not None and bucket >= _SHARD_MIN
    if shard:
        # Odd buckets pad up to a device multiple (the extra lanes are
        # the same inert dummy triple) instead of dropping to a single
        # device — a 10,001-lane batch must not forfeit the mesh.
        bucket = mesh_lane_pad(bucket, mesh)

    def stage(name):
        return rec.stage(name) if rec is not None \
            else contextlib.nullcontext()

    with stage("pack"), t.span(tracing.CRYPTO_PACK, lanes=bucket):
        if bucket > n:
            dp, dm, ds = _dummy_triple()
            pad = bucket - n
            pubs = list(pubs) + [dp] * pad
            msgs = list(msgs) + [dm] * pad
            sigs = list(sigs) + [ds] * pad
        packed = pack_batch(pubs, msgs, sigs, min_blocks)
    hit = count_compile("general", (bucket, packed["msg"].shape[1]))
    if rec is not None:
        rec.capacity = bucket
        rec.compile_hit = hit
        rec.bytes_h2d = _ledger.nbytes_of(packed) + \
            int(b_comb_tables().nbytes)
        if shard:
            d = int(mesh.devices.size)
            rec.n_devices = d
            rec.shard_lanes = [bucket // d] * d
            rec.active_devices = [str(dv) for dv in mesh.devices.flat]
    with stage("dispatch"), t.span(tracing.CRYPTO_DISPATCH, lanes=bucket):
        btab = b_comb_tables()
        if shard:
            import jax

            row_s, vec_s, repl_s = _shardings(mesh)
            packed = {
                k: jax.device_put(v, vec_s if v.ndim == 1 else row_s)
                for k, v in packed.items()
            }
            btab = jax.device_put(btab, repl_s)
            count_shard_lanes(mesh, bucket)
        return _kernel()(btab=btab, **packed)
