"""Expanded validator sets: per-key comb tables cached on device.

In consensus the SAME validators sign every block (the valset persists
across heights and changes only via ABCI validator updates —
reference: types/validator_set.go). The general kernel in `verify.py`
re-derives everything per verify: it decompresses each pubkey A (a
~250-squaring sqrt exponentiation), builds a 16-entry window table for
it, and pays 4 point doublings per 4-bit window of the challenge k.
All of that work depends only on A — so for a known validator set it
is done ONCE here and reused for every subsequent commit.

An ExpandedKeys holds, for each key, signed-digit comb tables of the
negated point:
    T[v, w, j] = j * 16^w * (-A_v)      (w < 69, j <= 8)
with the challenge recoded on device to digits d_w in [-8, 8]
(k = sum d_w 16^w); entry |d_w| is gathered and conditionally negated
by the digit sign. With these, [k](-A) needs NO doublings and NO
decompression at verify time — one table-gather + one point add per
window, the same shape as the fixed-base comb already used for [S]B.
Per-lane device work drops from ~4,200 field-mul equivalents to
~1,600 (69 adds + 69 comb adds + the R decompression, which is
per-signature and cannot be cached).

This is the analogue of ed25519-dalek's ExpandedPublicKey / the
precomputed-base tables every serious verifier uses for B — extended
to the whole validator set, which a consensus engine (unlike a generic
verifier) knows in advance. The reference has no equivalent: it pays
full per-signature cost every time (types/validator_set.go:683-705).

Layout notes (they dominated v1's performance): TPU int32 arrays tile
as (8, 128) over the trailing two dims, so a stored (..., 4, 22) table
pads 22 -> 128 and wastes 5.8x HBM (a 10k-val set OOMed at 23 GB).
Tables are therefore stored as (V*69*9, 128) rows — one point entry
per row, 88 payload ints + 40 pad — and the verify kernel fetches all
69 selected entries per lane before the window loop (69 small in-loop
gathers from a multi-GB buffer scalarize), by one of two reads. The
general one is ONE flat row-gather of 69 rows a lane: any lanes, any
keys, and the chip pays it per row (~10 ns a 512-byte row, ~5 % of
its bandwidth). A launch whose lanes are the set IN ORDER — key
indices strictly ascending, and the keys from the first one's block
of _BLOCK_KEYS to the last one no more than the launch has lanes: a
Commit verified against its own set, a light check's prefix — puts
key k's lane in slot k of the slab instead, reads the slab's rows as
they lie (all nine entries of every window, 9x the bytes and no
per-row cost) and selects by digit (_rows_in_order, a Pallas kernel).
The rule is read off the indices at pack time (_in_order_base); both
reads hand the window loop the same entries, bit for bit.
Memory: V * 69 * 9 * 512 B ≈ 318 KB/key — 3.3 GB for 10,240 keys.
"""

from __future__ import annotations

import contextlib
import functools
import hashlib
import threading
from collections import OrderedDict

import numpy as np

from . import ledger as _ledger
from . import verify as tv
from ...libs import jaxcache, tracing

_WINDOWS = 69  # scalar.DIGITS_K: folded challenge < 2^271
_ENTRIES = 9   # signed digits: |d| in 0..8
_ROW = 128     # table row width: 4 coords * NLIMB limbs, padded.
# i32 rep: 88 ints + 40 pad; f32 rep: 4 * 32 = 128 floats exactly.
# Expansion pays off only when the same set verifies repeatedly and the
# batch is big enough for the device path; below this many keys the
# general kernel is used instead.
MIN_EXPAND = 128

# -- key-range sharding crossover ------------------------------------
#
# Below the crossover the comb tables REPLICATE over the ('dp',) mesh
# (every gather chip-local, zero routing overhead — the right trade
# while the table fits one chip's HBM); above it they row-shard by
# KEY RANGE: device d holds the table rows of keys [d*K, (d+1)*K), and
# every launch routes lanes to their key's home device at pack time so
# the flat row-gather stays chip-local — per-chip HBM drops N× and the
# valset cap lifts to N× the single-chip budget. Configured via the
# [mesh] config section (node._build) or TM_TPU_SHARD_CROSSOVER.
_SHARD_CROSSOVER: int | None = None


def set_shard_crossover(n: int | None) -> None:
    """Valsets <= n replicate tables per chip; above n they key-range
    shard. None/0 restores auto (the single-chip table budget)."""
    global _SHARD_CROSSOVER
    _SHARD_CROSSOVER = int(n) if n else None


# CPU-backend policy cap for replicated tables: one DEFAULT build
# chunk's worth of keys. A deliberate constant rather than the live
# ExpandedKeys.BUILD_CHUNK attribute: tests shrink BUILD_CHUNK to
# force chunked builds, and the chunking knob must not silently
# re-route the build REGIME (replicated vs sharded vs refused).
_CPU_MAX_KEYS = 2048


# Table bytes per key (69 windows x 9 entries x one 128-wide 4-byte
# row = 317,952 B) and the share of a chip's memory the tables may
# take; the rest is the builder's and the verify kernels' temporaries.
_KEY_BYTES = _WINDOWS * _ENTRIES * _ROW * 4
_TABLE_SHARE = 0.75


@functools.cache
def _single_chip_max_keys() -> int:
    """Largest valset whose REPLICATED tables fit one device.

    Accelerators: _TABLE_SHARE of the memory the device itself reports
    (backend.device_memory_bytes: memory_stats()["bytes_limit"]; a
    device that reports none raises) over _KEY_BYTES — ~39k keys on a
    16 GB chip. CPU backend (tests / e2e nets /
    degraded nodes): one default build chunk — tables buy nothing
    there (no host->device wire to save), so big builds are pure
    cost."""
    import jax

    from . import backend as _backend

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return _CPU_MAX_KEYS
    return int(_TABLE_SHARE * _backend.device_memory_bytes(dev)) \
        // _KEY_BYTES


def shard_crossover_keys() -> int:
    import os

    if _SHARD_CROSSOVER is not None:
        return _SHARD_CROSSOVER
    env = os.environ.get("TM_TPU_SHARD_CROSSOVER")
    if env:
        try:
            val = int(env)
        except ValueError:
            # env is the lenient surface (config is the strict one):
            # a malformed value must not start raising mid-verify
            from .. import batch as _batch

            _batch.logger.warning(
                "ignoring malformed TM_TPU_SHARD_CROSSOVER=%r", env)
            val = 0
        if val:  # 0 means auto here too, like the config knob
            return val
    return _single_chip_max_keys()


@functools.cache
def _builder():
    import jax
    import jax.numpy as jnp

    from . import edwards as ed
    from .fieldsel import F as fe

    payload = 4 * fe.NLIMB
    assert payload <= _ROW

    @jax.jit
    def build(ab):
        """(V, 32) uint8 pubkeys -> ((V*69*9, 128) limb rows, (V,) ok)."""
        v = ab.shape[0]
        a_bytes = ab.astype(jnp.int32).T  # (32, V)
        a_sign = a_bytes[31] >> 7
        a_top = (a_bytes[31] & 0x7F)[None]
        a_y = fe.limbs_from_bytes(jnp.concatenate([a_bytes[:31], a_top]))
        pt, ok = ed.decompress(a_y, a_sign)
        neg_a = ed.neg(pt)

        def step(base, _):
            entries = [ed.identity(v), base]
            for _j in range(_ENTRIES - 2):
                entries.append(ed.add(entries[-1], base))
            row = jnp.stack(
                [jnp.stack(list(e), axis=0) for e in entries], axis=0
            )  # (9, 4, NLIMB, V)
            nxt = ed.double(ed.double(ed.double(ed.double(base))))
            return nxt, row

        _, rows = jax.lax.scan(step, neg_a, None, length=_WINDOWS)
        # (69, 9, 4, NLIMB, V): merge coord dims while V is still the
        # minor axis (clean tiling), pad the payload to a 128-wide row
        # (f32 rep: 4*32 = 128, zero pad), then rotate V major. Every
        # stored intermediate keeps a >=128-wide minor dim so nothing
        # hits the (8,128) tile blowup.
        rows = rows.reshape(_WINDOWS, _ENTRIES, payload, v)
        if payload != _ROW:
            rows = jnp.pad(
                rows, ((0, 0), (0, 0), (0, _ROW - payload), (0, 0)))
        rows = jnp.transpose(rows, (3, 0, 1, 2))  # (V, 69, 9, 128)
        return rows.reshape(v * _WINDOWS * _ENTRIES, _ROW), ok

    return build


@functools.cache
def _placer():
    """Write one build chunk's rows into the preallocated table buffer
    in place (the buffer is donated): a chunked build then peaks at
    the table plus ONE chunk, not the chunks plus their concatenation
    plus a trimmed copy."""
    import jax

    @functools.partial(jax.jit, donate_argnums=0)
    def place(tables, rows, start):
        return jax.lax.dynamic_update_slice(tables, rows, (start, 0))

    return place


# Windows processed per fori_loop iteration (69 must divide evenly:
# 1, 3, or 23). >1 unrolls the loop body, giving XLA ILP across
# windows at the cost of a bigger program. Default 3 from the round-4
# silicon A/B at 1,024 lanes: device exec 13.8 ms (wpi=1) -> 8.33 ms
# (wpi=3) -> 10.76 ms (wpi=23) — the mid unroll cuts the per-iteration
# fixed cost without blowing up the program.
WINDOWS_PER_ITER = int(__import__("os").environ.get(
    "TM_TPU_WINDOWS_PER_ITER", "3"))


# Keys a grid step of _rows_in_order reads: an in-order launch's slab
# starts on a block of them (a block's table rows, _BLOCK_KEYS * 621,
# are whole (8, 128) tiles), and _SLAB_LANES lanes of selected entries
# are turned limb-major at a time, so its lanes come in those.
# Measured on the v5e at 10,240 lanes over 10,000 keys (PERF.md §6,
# PR 43): 32 keys a step 5.71 ms, 16 keys 6.04, 8 keys 6.18, against
# 10.60 for the gather and its transpose; 3.18 GB at the ~590 GB/s a
# plain pass over HBM reaches is 5.4.
_BLOCK_KEYS = 32
_SLAB_LANES = 128
# two blocks of table rows in flight (2 x 10.2 MB), the selected
# entries of _SLAB_LANES lanes (4.5 MB), two output blocks
_ROWS_VMEM_BYTES = 48 << 20


def _interpret_pallas() -> bool:
    """Off the TPU (tests, CPU nodes) the Pallas kernel is
    interpreted: the same body as plain XLA operations."""
    import jax

    return jax.default_backend() != "tpu"


def _rows_in_order(atab, first, dmag, payload: int):
    """The table read of an in-order launch: lane i's key is
    first + i (first on a block of _BLOCK_KEYS), so the entries wanted
    lie in storage order. atab (V*69*9, 128) table rows, first (1,)
    int32, dmag (69, N) digit magnitudes -> (69, payload, N): for
    every window w and lane, the first `payload` ints of row
    (key*69 + w)*9 + dmag[w, lane], limb-major: what the flat
    row-gather, its transpose and its slice hand the window loop for
    those keys. Each grid step takes the rows of _BLOCK_KEYS keys as
    they lie in HBM (one contiguous DMA, double-buffered by the
    pipeline) and, eight keys and one window at a time, loads the nine
    candidate entries as nine sublane-strided vectors and keeps the
    one the digit names; once _SLAB_LANES lanes are selected they are
    transposed, a window at a time, into the output block. Steps past
    the table's last block (V is not a multiple of the lanes) read
    that block again: their lanes have no key and no verdict anyone
    reads. The digits come in as the program holds them, (windows,
    lanes), and are turned key-major in the kernel: handed in
    transposed, the operand's row-major layout is carried back by
    XLA's layout assignment through every field array of the program
    (lanes no longer minor: `decompress` 4.1 -> 15.7 ms, `msm` 19.0 ->
    33.4 on the v5e, PERF.md §6, PR 43)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = dmag.shape[1]
    key_rows = _WINDOWS * _ENTRIES
    kb, slab = _BLOCK_KEYS, _SLAB_LANES
    per = slab // kb                # grid steps to a slab of lanes
    assert n % slab == 0 and (kb * key_rows) % 8 == 0
    last = (atab.shape[0] // key_rows - 1) // kb

    def kernel(first_ref, d_ref, tab_ref, out_ref, picked, by_key):
        step = pl.program_id(0) % per

        @pl.when(step == 0)
        def _():
            by_key[...] = d_ref[...].T              # (lanes, windows)

        def eight_keys(g, carry):
            r0 = g * (8 * key_rows)
            at = pl.multiple_of(step * kb + g * 8, 8)
            d = by_key[pl.ds(at, 8), :]             # (8 keys, windows)
            for w in range(_WINDOWS):
                dw = d[:, w:w + 1]
                got = tab_ref[pl.ds(r0 + w * _ENTRIES, 8,
                                    stride=key_rows), :]
                for j in range(1, _ENTRIES):
                    rows = tab_ref[pl.ds(r0 + w * _ENTRIES + j, 8,
                                         stride=key_rows), :]
                    got = jnp.where(dw == j, rows, got)
                picked[w, pl.ds(at, 8), :] = got
            return carry

        jax.lax.fori_loop(0, kb // 8, eight_keys, 0)

        @pl.when(step == per - 1)
        def _():
            def limb_major(w, carry):
                out_ref[w] = picked[w].T[:payload, :]
                return carry

            jax.lax.fori_loop(0, _WINDOWS, limb_major, 0)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((_WINDOWS, payload, n), atab.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n // kb,),
            in_specs=[
                pl.BlockSpec((slab, slab),
                             lambda i, first: (0, i // per)),
                pl.BlockSpec(
                    (kb * key_rows, _ROW),
                    lambda i, first: (
                        jnp.minimum(first[0] // kb + i, last), 0)),
            ],
            out_specs=pl.BlockSpec((_WINDOWS, payload, slab),
                                   lambda i, first: (0, 0, i // per)),
            scratch_shapes=[
                pltpu.VMEM((_WINDOWS, slab, _ROW), atab.dtype),
                pltpu.VMEM((slab, slab), dmag.dtype)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_ROWS_VMEM_BYTES),
        interpret=_interpret_pallas(),
        name="comb_rows_in_order",
    )(first, jnp.pad(dmag, ((0, slab - _WINDOWS), (0, 0))), atab)


@functools.cache
def _xcore(wpi: int = WINDOWS_PER_ITER):
    """The shared verify body: everything after the padded message
    exists on device. Both front-ends trace through this — bytes
    (`_xkernel`: msg is the (N, W) buffer the host packed) and
    structured template+patch (`_skernel`: msg is the words
    assemble_core forms); sha512.challenge_words takes either.
    `in_order` (static): the launch's lanes are consecutive keys from
    idx[0] (ExpandedKeys._in_order_base), and the table entries are
    read in storage order (_rows_in_order), not gathered."""
    import jax
    import jax.numpy as jnp

    from . import edwards as ed
    from . import scalar as sc
    from . import sha512 as sh
    from .fieldsel import F as fe

    assert _WINDOWS % wpi == 0, "windows-per-iter must divide 69"
    L = fe.NLIMB  # payload layout: 4 coords of L limbs per table row

    def core(idx, akeys, sb, msg, nblocks, s_ok, key_ok, atab, btab,
             in_order=False):
        # The phase scopes follow the order the operations are traced
        # in (gather and msm open twice): moving one would change the
        # program and with it every cached executable.
        n = idx.shape[0]
        with jax.named_scope(tv.PHASE_GATHER):
            # Pubkey bytes gathered from the device-resident key array
            # — the host sends (N,) indices, not (N, 32) pubkey rows.
            ab = jnp.take(akeys, idx, axis=0)
        with jax.named_scope(tv.PHASE_SHA512):
            # SHA-512(R || A || M) + fold, exactly as the general
            # kernel.
            digest = sh.compress_blocks(
                sh.challenge_words(sb[:, :32], ab, msg), nblocks)
            digk = sc.fold_digest(
                sh.digest_bytes_le(digest))[::-1]  # LSB-first
            # Signed recode: nibbles (0..15) -> digits in [-8, 8] with
            # binary carries LSB -> MSB (nib + c >= 8 emits). The
            # folded value is < 2^271 so nibble 68 is 0 and the final
            # carry is absorbed (d_68 <= 1). Log-depth carry lookahead
            # instead of a 69-step sequential scan (fixed launch
            # latency).
            from . import field as _field

            cin, _ = _field.carry_lookahead(digk >= 8, digk >= 7)
            t = digk + cin.astype(jnp.int32)
            digk = t - 16 * (t >= 8).astype(jnp.int32)
        with jax.named_scope(tv.PHASE_DECOMPRESS):
            sig_bytes = sb.astype(jnp.int32).T  # (64, N)
        with jax.named_scope(tv.PHASE_MSM):
            digs = sc.bytes_to_nibbles(sig_bytes[32:])  # (64, N) LSB-first
            digs = jnp.concatenate(
                [digs, jnp.zeros((_WINDOWS - 64, n), jnp.int32)], axis=0
            )
        with jax.named_scope(tv.PHASE_DECOMPRESS):
            # R decompression (per-signature; the only uncacheable
            # curve work).
            r_sign = sig_bytes[31] >> 7
            r_top = (sig_bytes[31] & 0x7F)[None]
            r_y = fe.limbs_from_bytes(
                jnp.concatenate([sig_bytes[:31], r_top]))
            R, r_ok = ed.decompress(r_y, r_sign)
            neg_r = ed.neg(R)

        with jax.named_scope(tv.PHASE_GATHER):
            # Every window's selected entry: ONE flat row-gather,
            # or the rows as they lie where the lanes are consecutive
            # keys.
            dsign = digk < 0
            dmag = jnp.abs(digk)  # (69, N) in 0..8
            if in_order:
                sel = _rows_in_order(atab, idx[:1], dmag, 4 * L)
            else:
                flat = (
                    idx[None, :] * (_WINDOWS * _ENTRIES)
                    + jnp.arange(_WINDOWS, dtype=jnp.int32)[:, None]
                    * _ENTRIES
                    + dmag
                )  # (69, N)
                sel = jnp.take(atab, flat.reshape(-1), axis=0)  # (69*N, 128)
                # ONE transpose to the kernel's limb-major layout;
                # slicing any pad ints fuses into it. Doing this per
                # window instead (69 small transposes out of a
                # lane-major buffer) costs ~60 ms of device time at
                # 16k lanes — measured, not hypothetical.
                sel = jnp.transpose(
                    sel.reshape(_WINDOWS, n, _ROW), (0, 2, 1))
                sel = sel[:, : 4 * L, :]  # (69, 4L, N)

        def one_window(w, acc_a, acc_b):
            e = jax.lax.dynamic_index_in_dim(sel, w, 0, keepdims=False)
            neg = jax.lax.dynamic_index_in_dim(dsign, w, 0, keepdims=False)
            # -(x, y, z, t) = (-x, y, z, -t), applied per digit sign.
            qx = jnp.where(neg[None], fe.neg(e[:L]), e[:L])
            qt = jnp.where(neg[None], fe.neg(e[3 * L:]), e[3 * L:])
            acc_a = ed.add(acc_a, ed.Point(qx, e[L:2 * L], e[2 * L:3 * L], qt))
            ds = jax.lax.dynamic_index_in_dim(digs, w, 0, keepdims=False)
            bw = jax.lax.dynamic_index_in_dim(btab, w, 0, keepdims=False)
            bx, by, bt = ed.select_const(bw, ds)
            acc_b = ed.add_z1(acc_b, bx, by, bt)
            return acc_a, acc_b

        def body(i, accs):
            acc_a, acc_b = accs
            for j in range(wpi):  # unrolled in the traced program
                acc_a, acc_b = one_window(i * wpi + j, acc_a, acc_b)
            return (acc_a, acc_b)

        with jax.named_scope(tv.PHASE_MSM):
            acc_a, acc_b = jax.lax.fori_loop(
                0, _WINDOWS // wpi, body, (ed.identity(n), ed.identity(n))
            )
            v = ed.add(ed.add(acc_a, acc_b), neg_r)
            v = ed.double(ed.double(ed.double(v)))
        with jax.named_scope(tv.PHASE_COMPARE):
            return (
                ed.is_identity(v)
                & r_ok
                & jnp.asarray(s_ok)
                & key_ok[idx]
            )

    return core


@functools.cache
def _xkernel(wpi: int = WINDOWS_PER_ITER):
    import jax

    core = _xcore(wpi)

    @functools.partial(jax.jit, static_argnames=("in_order",))
    def kernel(idx, akeys, sb, msg, nblocks, s_ok, key_ok, atab, btab,
               *, in_order=False):
        return core(idx, akeys, sb, msg, nblocks, s_ok, key_ok, atab,
                    btab, in_order)

    return kernel


@functools.cache
def _xkernel_sharded(wpi: int = WINDOWS_PER_ITER):
    """Key-range-sharded front-end: every per-lane array and the comb
    table carry a leading device axis (sharded P('dp')); vmapping the
    UNCHANGED verify body over it makes each device run the core on
    its local (lanes, key-range) block — local indices address local
    table rows, so the flat row-gather never crosses chips (btab, the
    fixed-base comb, replicates: every device needs every window)."""
    import jax

    core = _xcore(wpi)

    @jax.jit
    def kernel(idx, akeys, sb, msg, nblocks, s_ok, key_ok, atab, btab):
        return jax.vmap(
            core, in_axes=(0, 0, 0, 0, 0, 0, 0, 0, None))(
            idx, akeys, sb, msg, nblocks, s_ok, key_ok, atab, btab)

    return kernel


@functools.cache
def assemble_core():
    """The structured message-assembly body as a traceable function:
    (pre, pre_len, suf, suf_len, patch, split, patch_len, group,
    width) -> (words uint32 (width/8, 2, N), nblocks (N,)). Builds each
    lane's sign bytes ON DEVICE from commit-wide templates plus a
    <=24-byte per-lane timestamp patch (types/sign_batch.py layout:
    outer_varint ‖ pre[group] ‖ ts_field ‖ suf[group]), applies the
    SHA-512 padding tail and forms the hash's words
    (sha512.challenge_words puts R ‖ A in front). Shared by `_skernel`
    (expanded-table path) and crypto/tpu/resident.py's arena kernels
    (general-kernel path over device-resident buffers).

    Lanes are the minor axis throughout, (rows, N), and no element is
    gathered: a lane's template rows are selected group by group, and
    a source lands at its per-lane row offset by a barrel shift, one
    select of a statically shifted copy per bit of the offset. (The
    same bytes placed by take_along_axis over an (N, width) index
    plane were 60 % of the 10,240-lane kernel: PERF.md §6, PR 25.)"""
    import jax
    import jax.numpy as jnp

    from . import sha512 as sh

    def by_group(table, group):
        """(K, C) template rows -> (C, N): row group[lane] of the
        table, one lane a column (group is the (1, N) row)."""
        cols = table.T.astype(jnp.int32)
        out = jnp.zeros((cols.shape[0], group.shape[1]), jnp.int32)
        for g in range(cols.shape[1]):
            out = jnp.where(group == g, cols[:, g:g + 1], out)
        return out

    def live(src, lo, hi):
        """src (C, N) with each lane's rows outside [lo, hi) zeroed,
        so that placed sources can be OR-ed together."""
        j = jnp.arange(src.shape[0], dtype=jnp.int32)[:, None]
        return jnp.where((j >= lo) & (j < hi), src, 0)

    def place(src, offset, bound, width):
        """src (C, N) -> (width, N): lane l's row i lands on row
        i + offset[l], for 0 <= offset <= bound (static). Stage k
        moves the lanes whose offset has bit k set down by 2^k rows;
        the plane grows as it goes, so the early stages are narrow."""
        x = src
        for k in range(bound.bit_length()):
            step = 1 << k
            rows = min(x.shape[0] + step, width)
            moved = jnp.pad(x, ((step, 0), (0, 0)))[:rows]
            x = jnp.pad(x, ((0, rows - x.shape[0]), (0, 0)))
            x = jnp.where((offset & step) != 0, moved, x)
        return jnp.pad(x, ((0, width - x.shape[0]), (0, 0)))

    @jax.named_scope(tv.PHASE_ASSEMBLE)
    def assemble(pre, pre_len, suf, suf_len, patch, split, patch_len,
                 group, width):
        # the two live bytes of the 16-byte bit length
        assert (64 + width) * 8 < 1 << 16
        pw, tw = pre.shape[1], patch.shape[1]
        # per-lane scalars as (1, N) rows, beside the (rows, N) planes
        group = group[None, :]
        p_len = by_group(pre_len[:, None], group)
        s_len = by_group(suf_len[:, None], group)
        a = split[None, :].astype(jnp.int32)
        t_end = patch_len[None, :].astype(jnp.int32)          # a + b
        c3 = t_end + p_len + s_len                            # = mlen
        patch_t = patch.T.astype(jnp.int32)                   # (TW, N)
        msg = (
            place(live(patch_t, 0, a), 0, 0, width)
            | place(live(by_group(pre, group), 0, p_len), a, 2, width)
            | place(live(patch_t, a, t_end), p_len, pw, width)
            | place(live(by_group(suf, group), 0, s_len),
                    t_end + p_len, pw + tw, width))
        j = jnp.arange(width, dtype=jnp.int32)[:, None]       # (W, 1)
        msg = jnp.where(j == c3, 0x80, msg)
        # SHA-512 padding tail: 16-byte big-endian bit length at the
        # end of the lane's last block (bit length < 2^16 here, so
        # only the low 2 bytes are ever nonzero).
        nblocks = (64 + c3 + 17 + 127) // 128
        bitlen = (64 + c3) * 8
        last = nblocks * 128 - 64 - 1
        msg = jnp.where(j == last, bitlen & 0xFF,
                        jnp.where(j == last - 1, bitlen >> 8, msg))
        return sh.rows_to_words(msg), nblocks[0]

    return assemble


# Up to this many lanes the structured program takes the field
# operations as calls (field.as_calls): a shape's first launch then
# costs ~5 s of tracing and lowering on the chip's host where the
# inlined form costs ~25-50, and these are the launches a node meets
# in many shapes (a LastCommit, a light commit, a block's evidence, a
# fast-sync window of a 1,000-validator chain). Measured on the v5e
# (PERF.md §6, PR 35): the called form runs as fast at 1,024 and
# 2,048 lanes (4.30 / 10.5 ms for 4.32 / 10.55) and SLOWER above:
# 28.8 ms for 25.95 at 7,168 lanes, 39.8 for 36.1 at 10,240 (XLA
# prefetches the main loop's operands otherwise). Under the limit a
# launch is verified ahead or is a few ms of a block's ~80-180 ms of
# host work, and a 35 s stall at a shape's first launch is the larger
# cost; over it the launch is a 10,000-validator commit, whose latency
# IS the kernel's, and the program stays the inlined one.
_CALLS_MAX_LANES = 8192


@jaxcache.one_program
def _skernel(wpi: int = WINDOWS_PER_ITER):
    """Structured front-end: assemble each lane's padded message ON
    DEVICE, as the words SHA-512 reads (assemble_core), then verify
    through the expanded-table body (_xcore). Per-lane transfer drops
    from ~190 B of sign bytes to the patch + two ints; the templates
    ship once per launch."""
    import jax

    from .fieldsel import F as fe

    core = _xcore(wpi)
    assemble = assemble_core()

    @functools.partial(jax.jit, static_argnames=("width", "in_order"))
    def skernel(idx, akeys, sb, s_ok, key_ok, atab, btab,
                pre, pre_len, suf, suf_len, patch, split, patch_len,
                group, *, width, in_order=False):
        with fe.as_calls(idx.shape[0] <= _CALLS_MAX_LANES):
            msg, nblocks = assemble(pre, pre_len, suf, suf_len, patch,
                                    split, patch_len, group, width)
            return core(idx, akeys, sb, msg, nblocks, s_ok, key_ok,
                        atab, btab, in_order)

    return skernel


@functools.cache
def _skernel_sharded(wpi: int = WINDOWS_PER_ITER):
    """_skernel over key-range-sharded tables: per-lane arrays carry a
    leading device axis; the commit-wide templates (and btab)
    replicate — every device assembles its own lanes' sign bytes from
    the same templates, then verifies against its local key range."""
    import jax

    core = _xcore(wpi)
    assemble = assemble_core()

    @functools.partial(jax.jit, static_argnames=("width",))
    def skernel(idx, akeys, sb, s_ok, key_ok, atab, btab,
                pre, pre_len, suf, suf_len, patch, split, patch_len,
                group, *, width):
        def one(idx, akeys, sb, s_ok, key_ok, atab, patch, split,
                patch_len, group):
            msg, nblocks = assemble(pre, pre_len, suf, suf_len, patch,
                                    split, patch_len, group, width)
            return core(idx, akeys, sb, msg, nblocks, s_ok, key_ok,
                        atab, btab)

        return jax.vmap(one)(idx, akeys, sb, s_ok, key_ok, atab,
                             patch, split, patch_len, group)

    return skernel


@contextlib.contextmanager
def _phase_names_in_key():
    """What a structured launch is lowered and compiled under: the
    operations' names (`op_name`: the ed25519.* scope and the
    primitive) are in the module and in the persistent cache's key, and
    no file, line or traceback is. An executable loaded from the cache
    was then compiled from these very names, whoever compiled it, so
    structured_phases() reads them off the launch's own executable. A
    line shift in these files still hits; renaming or moving a scope
    compiles anew. Both settings are per thread and are read only
    when a shape is lowered and looked up, on its first launch."""
    from jax._src import config as jax_config  # no public per-thread form

    with jax_config.traceback_in_locations_limit(0), \
            jax_config.compilation_cache_include_metadata_in_key(True):
        yield


def _aval(a):
    """Shape, dtype and placement of a launch argument: what `.lower()`
    needs in its stead. The placement only of an array that is
    committed to it, as a call takes it: the tables of one chip are
    not (they follow the default device), and with a sharding named
    for them the program would lower, and key, as another one."""
    import jax

    placed = getattr(a, "committed", False)
    return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                sharding=a.sharding if placed else None)


# what an in-order program's shape carries in tv._COMPILED_SHAPES
# beside the gathering program's of the same lanes and width
_ROWS_KEY = {False: (), True: ("in_order",)}


def _count_compile(kernel: str, shape: tuple) -> None:
    """tv.count_compile, its answer put on the launch record open
    around this call (the ledger's compile_cache hit|miss)."""
    hit = tv.count_compile(kernel, shape)
    rec = _ledger.current()
    if rec is not None:
        rec.compile_hit = hit


class _RoutedVerdicts:
    """Device verdicts of a lane-routed sharded launch, presented in
    the caller's original lane order (quacks like the device array
    _traced_verify expects: block_until_ready + np.asarray)."""

    def __init__(self, dev, slot: np.ndarray):
        self._dev = dev
        self._slot = slot

    def block_until_ready(self):
        self._dev.block_until_ready()
        return self

    def __array__(self, dtype=None, copy=None):
        out = np.asarray(self._dev).reshape(-1)[self._slot]
        return out.astype(dtype) if dtype is not None else out


class ExpandedKeys:
    """Device-resident comb tables for a fixed list of ed25519 pubkeys."""

    # Keys per build launch. The builder materializes ~3 stacked
    # copies of its output (scan rows + pad + transpose) — unchunked
    # at 10k keys that is ~9 GB of transient HBM on top of the 3.3 GB
    # result. Chunking bounds it: compiled for the v5e at 2,048 keys
    # the builder has a 651 MB output and 1.9 GB of temporaries
    # (tests/test_chip_compile.py). Chunks land in one preallocated
    # buffer and the per-key row blocks are contiguous, so the flat
    # row-gather indexing is unchanged.
    BUILD_CHUNK = 2048

    def __init__(self, pubkeys: list[bytes]):
        import jax.numpy as jnp

        self.pubkeys = tuple(bytes(p) for p in pubkeys)
        assert all(len(p) == 32 for p in self.pubkeys)
        a_raw = np.frombuffer(b"".join(self.pubkeys), np.uint8).reshape(-1, 32)
        v = len(self.pubkeys)
        self.sharded = False
        self.n_shards = 1
        self.keys_per_shard = v
        self._reshard_lock = threading.Lock()
        # Build over the EFFECTIVE mesh (full mesh minus evicted
        # devices): a build while degraded shards over the survivors,
        # and _maybe_reshard() rebuilds live when the set changes.
        self.mesh = tv.effective_mesh()
        # Shard above the crossover — or above the single-chip budget
        # regardless of the crossover: an operator raising the
        # crossover past the budget must degrade to sharding, not to a
        # per-commit ValueError that churns the breaker.
        if self.mesh is not None and (
                v > shard_crossover_keys()
                or v > _single_chip_max_keys()):
            self._build_sharded(a_raw)
            return
        if v > _single_chip_max_keys():
            raise ValueError(
                f"{v}-key expanded build exceeds the single-chip table "
                f"budget ({_single_chip_max_keys()} keys) and no mesh "
                "is available for key-range sharding")
        tables, ok = self._build_tables(a_raw)
        # Small sets: REPLICATE the tables over the ('dp',) mesh and
        # shard lanes at launch (same scheme as verify_batch). Lane
        # digits address arbitrary table rows, so replication keeps
        # every gather chip-local at 69 * 512 B/lane with zero routing
        # overhead; HBM cost is the full table per chip (~318 KB/key).
        # Above the shard crossover, _build_sharded row-shards by KEY
        # RANGE instead and launches route lanes to home devices.
        akeys = jnp.asarray(a_raw)
        if self.mesh is not None:
            import jax

            _, _, repl_s = tv._shardings(self.mesh)
            tables = jax.device_put(tables, repl_s)
            ok = jax.device_put(ok, repl_s)
            akeys = jax.device_put(akeys, repl_s)
        self.tables = tables  # keep on device
        self.key_ok = ok
        # Pubkey bytes device-resident beside the tables: verify
        # launches send (N,) indices instead of (N, 32) pubkey rows.
        self.akeys = akeys
        self._register_hbm()

    def _register_hbm(self) -> None:
        """Device-resident comb tables + key rows claim their bytes in
        the HBM accounting registry (ledger.register_hbm): replicated
        tables cost the FULL table on every chip; key-range-sharded
        builds one range block per chip."""
        try:
            nbytes = int(self.tables.nbytes) + int(self.akeys.nbytes) \
                + int(self.key_ok.nbytes)
            if self.sharded:
                per = nbytes // max(self.n_shards, 1)
                for d in list(self.mesh.devices.flat):
                    _ledger.register_hbm("table_shard", str(d), per)
            elif self.mesh is not None:
                for d in list(self.mesh.devices.flat):
                    _ledger.register_hbm("comb_tables", str(d), nbytes)
            else:
                _ledger.register_hbm(
                    "comb_tables", _ledger.default_device_str(), nbytes)
        except Exception:  # pragma: no cover - accounting never fatal
            pass

    def _release_hbm(self) -> None:
        """Drop this build's bytes from the HBM accounting registry
        (register_hbm with 0 bytes unregisters): a live reshard must
        not leave the old placement's bytes attributed to devices —
        possibly evicted ones — that no longer hold a shard."""
        try:
            kind = "table_shard" if self.sharded else "comb_tables"
            if self.mesh is not None:
                for d in list(self.mesh.devices.flat):
                    _ledger.register_hbm(kind, str(d), 0)
            else:
                _ledger.register_hbm(
                    kind, _ledger.default_device_str(), 0)
        except Exception:  # pragma: no cover - accounting never fatal
            pass

    def _maybe_reshard(self) -> None:
        """Live fabric reshard: when the effective mesh (full mesh
        minus breaker-evicted devices) no longer matches the mesh this
        build is placed on — a device was just evicted, or a half-open
        probe re-admitted one — rebuild the placement over the
        SURVIVING device set in place. Key-range-sharded tables
        rebuild D -> D' shards from the pubkey bytes (recomputable;
        the raw keys are kept); replicated tables re-place onto the
        new mesh. Old shard HBM is released from the accounting
        registry first and the new placement re-registers. Verdicts
        are unchanged: same keys, same kernels — only device placement
        and per-device key ranges move. Breaker events are rare, so
        the lock never contends on the steady-state path (the
        identity fast-path above it is lock-free)."""
        if self.mesh is None:
            return
        want = tv.effective_mesh()
        if want is self.mesh:
            return
        with self._reshard_lock:
            want = tv.effective_mesh()
            if want is self.mesh:
                return
            if want is None:
                # Fewer than 2 survivors: no mesh can form. Keep the
                # current placement — backend-wide escalation (all
                # devices evicted) is handled by mark_device_failed.
                return
            have = [str(d) for d in self.mesh.devices.flat]
            if [str(d) for d in want.devices.flat] == have:
                self.mesh = want  # same devices, fresher mesh object
                return
            import time as _time

            t0 = _time.perf_counter()
            self._release_hbm()
            self.mesh = want
            if self.sharded:
                a_raw = np.frombuffer(
                    b"".join(self.pubkeys), np.uint8).reshape(-1, 32)
                self._build_sharded(a_raw)
            else:
                import jax

                _, _, repl_s = tv._shardings(want)
                self.tables = jax.device_put(self.tables, repl_s)
                self.key_ok = jax.device_put(self.key_ok, repl_s)
                self.akeys = jax.device_put(self.akeys, repl_s)
                self._register_hbm()
            dt = _time.perf_counter() - t0
            try:
                from ...libs.metrics import tpu_metrics

                tpu_metrics().reshard_seconds.observe(dt)
            except Exception:  # pragma: no cover - metrics never fatal
                pass
            from .. import batch as cbatch

            cbatch.logger.warning(
                "live fabric reshard: %d-key tables rebuilt over %d "
                "device(s) in %.3fs", len(self.pubkeys),
                int(want.devices.size), dt)

    def _build_tables(self, a_raw: np.ndarray, device=None):
        """Chunked comb-table build: (V, 32) pubkey rows ->
        ((V*69*9, 128) rows, (V,) ok). Builder launches run on the
        default device (BUILD_CHUNK bounds their transients); with
        `device` set, each chunk's rows move to that device as they
        land and the table is assembled THERE — the sharded build's
        per-range blocks must not pile up on the default device."""
        import jax.numpy as jnp

        def park(t):
            if device is None:
                return t
            import jax

            return jax.device_put(t, device)

        v = a_raw.shape[0]
        if v <= self.BUILD_CHUNK:
            tv.count_compile("table_builder", (v,))
            t, o = _builder()(jnp.asarray(a_raw))
            return park(t), o
        # Every launch builds one full chunk (one compiled shape; the
        # last is zero-padded) and its rows are written into ONE
        # preallocated buffer, each chunk freed as it is consumed:
        # the peak is the table + one chunk + the builder's
        # temporaries. The padding keys' rows are dropped before they
        # land (up to chunk-1 keys x ~318 KB each would otherwise sit
        # in HBM — replicated per mesh chip — for the cache lifetime).
        chunk = self.BUILD_CHUNK
        rows_per_key = _WINDOWS * _ENTRIES
        tables, ok_parts = None, []
        tv.count_compile("table_builder", (chunk,))
        for s in range(0, v, chunk):
            here = min(chunk, v - s)
            keys = np.zeros((chunk, 32), np.uint8)
            keys[:here] = a_raw[s:s + here]
            t, o = _builder()(jnp.asarray(keys))
            if here < chunk:
                t = t[: here * rows_per_key]
            t = park(t)
            if tables is None:
                tables = jnp.zeros((v * rows_per_key, _ROW), t.dtype,
                                   device=device)
            tables = _placer()(tables, t, np.int32(s * rows_per_key))
            ok_parts.append(o)
        ok = jnp.concatenate(ok_parts)[:v]
        return tables, ok

    def _build_sharded(self, a_raw: np.ndarray) -> None:
        """Key-range-sharded build: pad the valset to D*K keys, build
        each K-key range chunk by chunk (builder launches on the
        default device with BUILD_CHUNK-bounded transients, each
        chunk's rows parked on the range's HOME device as they land),
        and assemble the per-device blocks into ONE global
        (D, K*69*9, 128) array sharded P('dp') on axis 0 — no chip
        ever holds more than its own range. Lifts the valset cap to
        D × the single-chip budget and cuts per-chip HBM D×; launches
        route lanes to home devices (_route) so the flat row-gather
        stays chip-local."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self.mesh
        v = a_raw.shape[0]
        devs = list(mesh.devices.flat)
        d_n = len(devs)
        k = -(-v // d_n)
        vp = k * d_n
        padded = np.zeros((vp, 32), np.uint8)
        padded[:v] = a_raw
        rows_per_key = _WINDOWS * _ENTRIES
        sh = NamedSharding(mesh, P("dp"))
        parts = []
        ok_np = np.zeros((d_n, k), bool)
        for d in range(d_n):
            # chunks park on the HOME device as they land, so the
            # default device's transient stays one BUILD_CHUNK deep
            # regardless of shard or mesh size
            t, o = self._build_tables(padded[d * k:(d + 1) * k],
                                      device=devs[d])
            parts.append(t.reshape(1, k * rows_per_key, _ROW))
            ok_np[d] = np.asarray(o)
        # Padding keys never verify (idx is asserted < len(pubkeys)
        # and pad LANES are discarded by the slot scatter), but keep
        # their ok flags False for hygiene.
        ok_np.reshape(-1)[v:] = False
        self.tables = jax.make_array_from_single_device_arrays(
            (d_n, k * rows_per_key, _ROW), sh, parts)
        self.key_ok = jax.device_put(jnp.asarray(ok_np), sh)
        self.akeys = jax.device_put(
            jnp.asarray(padded.reshape(d_n, k, 32)), sh)
        self.sharded = True
        self.n_shards = d_n
        self.keys_per_shard = k
        self._register_hbm()
        try:
            from ...libs.metrics import tpu_metrics

            tpu_metrics().table_shard_bytes.set(int(parts[0].nbytes))
        except Exception:  # pragma: no cover - metrics never fatal
            pass

    def __len__(self) -> int:
        return len(self.pubkeys)

    def _check_idx(self, indices, n_sigs) -> np.ndarray:
        n = len(indices)
        assert n_sigs == n
        idx = np.asarray(indices, np.int32)
        assert n <= tv._MAX_BATCH, "split huge batches at the call site"
        assert idx.min() >= 0 and idx.max() < len(self.pubkeys)
        return idx

    @staticmethod
    def _sig_rows(sigs, pad: int) -> tuple[np.ndarray, np.ndarray]:
        """(bucket, 64) signature rows + per-lane well-formedness.

        Per-lane length check, vectorized (map(len) runs the loop in
        C). An AGGREGATE total-length shortcut would be unsound:
        two adjacent malformed sigs of 63+65 bytes cancel out and
        every following lane's bytes shift — an accept/reject
        divergence between nodes on adversarial commits."""
        n = len(sigs)
        lens = np.fromiter(map(len, sigs), np.int64, count=n)
        well_formed = lens == 64
        if not well_formed.all():
            sigs = [s if ok else b"\0" * 64
                    for s, ok in zip(sigs, well_formed)]
        joined = b"".join(sigs) + b"\0" * (64 * pad)
        return (np.frombuffer(joined, np.uint8).reshape(n + pad, 64),
                well_formed)

    @staticmethod
    def _bucket(n: int) -> int:
        """Powers of two up to 1024, then multiples of 1024 (a
        10,240-lane commit runs at exactly 10,240 instead of padding
        1.6x to 16,384; valset sizes are stable so the shape cache
        stays small)."""
        if n <= 1024:
            bucket = tv._MIN_BATCH
            while bucket < n:
                bucket <<= 1
            return bucket
        return (n + 1023) // 1024 * 1024

    def _prepare(self, indices, msgs, sigs):
        """Host side of verify: validate, pad to a bucket, pack bytes.

        Split from the launch so callers (bench.py) can attribute
        host-packing vs device time separately."""
        n = len(indices)
        assert len(msgs) == n
        idx = self._check_idx(indices, len(sigs))
        # Key-range-sharded tables bucket PER DEVICE inside _route —
        # pre-padding here would home every pad lane (idx 0) on device
        # 0 and inflate the common per-device bucket for all shards.
        bucket = n if self.sharded else self._bucket(n)
        base = self._in_order_base(idx, bucket)
        if base is not None:
            sig_raw, well_formed = self._sig_rows(sigs, 0)
            idx, packed, slots = self._in_slots(
                idx, base, bucket, tv.pack_sig_msg(sig_raw, msgs))
            return idx, packed, well_formed, slots
        pad = bucket - n
        sig_raw, well_formed = self._sig_rows(sigs, pad)
        if pad:
            idx = np.concatenate([idx, np.zeros(pad, np.int32)])
            msgs = list(msgs) + [b""] * pad
        packed = tv.pack_sig_msg(sig_raw, msgs)
        return idx, packed, well_formed, None

    def _in_order_base(self, idx: np.ndarray, bucket: int) -> int | None:
        """The rule that picks the table read, off the launch's key
        indices alone: the first key of the slab an IN-ORDER launch
        reads (_rows_in_order), None for a launch that gathers. In
        order: indices strictly ascending (each key at most once, in
        the set's order) and the keys from the first one's block to
        the last one no more than the launch's lanes, so that a lane
        per key of the slab adds no lane of device work. A Commit
        verified against its own set is (absent votes leave gaps), a
        light check's prefix is; votes in arrival order, a window of
        commits (keys repeat) and a sparse subset (span >> lanes) are
        not. Launches whose lanes go to several devices (key-range-
        sharded tables: _route; a mesh at _SHARD_MIN lanes:
        _shard_args) keep the gather, which is local to each; so do
        lanes that are not whole slabs (verify_structured's `lanes`)
        and a set smaller than one block."""
        if self.sharded or bucket % _SLAB_LANES or (
                self.mesh is not None and bucket >= tv._SHARD_MIN) \
                or len(self.pubkeys) < _BLOCK_KEYS:
            return None
        base = int(idx[0]) // _BLOCK_KEYS * _BLOCK_KEYS
        if int(idx[-1]) - base >= bucket or not (idx[1:] > idx[:-1]).all():
            return None
        return base

    def _in_slots(self, idx, base: int, bucket: int, per_lane: dict):
        """An in-order launch's lanes: key k's lane in slot k - base
        of `bucket`, every per-lane array scattered into zeros (a slot
        without a signature carries s_ok False, as a pad lane's
        verdict is never read). Returns (the program's idx: the slab's
        keys, clipped to the set's last; the arrays; the slots the
        caller's lanes took, where their verdicts are read)."""
        slots = idx - base
        keys = np.minimum(base + np.arange(bucket, dtype=np.int32),
                          len(self.pubkeys) - 1)
        out = {}
        for name, a in per_lane.items():
            out[name] = np.zeros((bucket,) + a.shape[1:], a.dtype)
            out[name][slots] = a
        return keys, out, slots

    def _shard_args(self, idx, fields, repl_keys=()):
        """Shared mesh dispatch for both launch forms (replicated
        tables): lane-shard the per-lane arrays over the ('dp',) mesh
        when one exists (tables, comb constants, and any `repl_keys`
        fields replicated; verdict gather is the only cross-chip
        traffic). Odd buckets pad up to a device multiple — the pad
        lanes carry zero signatures (s_ok False) and are discarded by
        the caller's [:n] slice — instead of forfeiting the mesh."""
        btab = tv.b_comb_tables()
        # the mesh the tables are PLACED on (effective mesh at build /
        # last reshard) — lanes must shard over the same device set
        mesh = self.mesh
        bucket = idx.shape[0]
        if mesh is not None and bucket >= tv._SHARD_MIN:
            import jax

            pad = tv.mesh_lane_pad(bucket, mesh) - bucket
            if pad:
                idx = np.concatenate([idx, np.zeros(pad, np.int32)])
                fields = {
                    k: v if k in repl_keys else np.pad(
                        v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                    for k, v in fields.items()
                }
            row_s, vec_s, repl_s = tv._shardings(mesh)
            idx = jax.device_put(idx, vec_s)
            fields = {
                k: jax.device_put(
                    v, repl_s if k in repl_keys
                    else (vec_s if v.ndim == 1 else row_s))
                for k, v in fields.items()
            }
            btab = jax.device_put(btab, repl_s)
            tv.count_shard_lanes(mesh, bucket + pad)
        return idx, fields, btab

    def _route(self, idx, per_lane: dict):
        """Lane → home-device routing at pack time (key-range-sharded
        tables): stable-sort lanes by their key's home device, pad
        every device to a common per-device lane bucket, and rebase
        indices into the device's local key range. Returns the routed
        (D, n_local[, ...]) device arrays plus the flat slot map that
        restores original lane order on readback. Pad lanes carry
        local index 0 and zero signatures (s_ok False) — inert, and
        dropped by the slot scatter anyway. n_local is the LARGEST
        shard's bucketed count: balanced batches (commit lanes are
        distinct validators) run ~N/D per chip, while a pathological
        all-one-range batch pads every chip to the full batch — skewed
        ad-hoc index sets belong below the shard crossover."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        d_n, k = self.n_shards, self.keys_per_shard
        bucket = idx.shape[0]
        home = idx // k
        order = np.argsort(home, kind="stable")
        counts = np.bincount(home, minlength=d_n)
        n_local = self._bucket(max(int(counts.max()), 1))
        local_idx = np.zeros((d_n, n_local), np.int32)
        routed = {
            name: np.zeros((d_n, n_local) + a.shape[1:], a.dtype)
            for name, a in per_lane.items()
        }
        slot = np.zeros(bucket, np.int64)
        off = 0
        for d in range(d_n):
            sel = order[off:off + counts[d]]
            local_idx[d, :counts[d]] = idx[sel] - d * k
            for name, a in per_lane.items():
                routed[name][d, :counts[d]] = a[sel]
            slot[sel] = d * n_local + np.arange(counts[d])
            off += counts[d]
        # padding included — every device executes n_local lanes —
        # the same semantics as the other dispatch sites
        tv.count_shard_lanes(self.mesh, n_local * d_n)
        try:
            from ...libs.metrics import tpu_metrics

            # occupancy against the lanes the mesh actually executes
            # (d_n * n_local), so routing skew shows up instead of
            # reading ~D× too healthy
            tpu_metrics().batch_occupancy.observe(
                bucket / (n_local * d_n))
        except Exception:  # pragma: no cover - metrics never fatal
            pass
        sh = NamedSharding(self.mesh, P("dp"))
        repl_s = NamedSharding(self.mesh, P())
        lidx = jax.device_put(local_idx, sh)
        routed = {name: jax.device_put(a, sh)
                  for name, a in routed.items()}
        btab = jax.device_put(tv.b_comb_tables(), repl_s)
        return lidx, routed, btab, repl_s, slot

    def _launch(self, idx, packed, in_order=False):
        """Device side of verify: one kernel launch over packed lanes
        (`in_order`: idx is _in_slots')."""
        if self.sharded:
            lidx, routed, btab, _repl_s, slot = self._route(idx, packed)
            _count_compile(
                "expanded_sharded",
                (self.n_shards, lidx.shape[1], routed["msg"].shape[2]))
            out = _xkernel_sharded(WINDOWS_PER_ITER)(
                idx=lidx,
                akeys=self.akeys,
                key_ok=self.key_ok,
                atab=self.tables,
                btab=btab,
                **routed,
            )
            return _RoutedVerdicts(out, slot)
        idx, packed, btab = self._shard_args(idx, packed)
        # count at the POST-padding shape: mesh_lane_pad may merge two
        # requested buckets into one compiled shape
        _count_compile("expanded",
                       (idx.shape[0], packed["msg"].shape[1])
                       + _ROWS_KEY[in_order])
        return _xkernel(WINDOWS_PER_ITER)(
            idx=idx,
            akeys=self.akeys,
            key_ok=self.key_ok,
            atab=self.tables,
            btab=btab,
            in_order=in_order,
            **packed,
        )

    def verify(self, indices, msgs, sigs) -> np.ndarray:
        """Verify (self.pubkeys[indices[i]], msgs[i], sigs[i]) lanes.

        One kernel launch (padded to a power-of-two bucket); semantics
        identical to verify.verify_batch on the same triples.
        """
        n = len(indices)
        if n == 0:
            return np.zeros(0, bool)
        self._maybe_reshard()

        def prepare():
            idx, packed, well_formed, slots = self._prepare(
                indices, msgs, sigs)
            return (idx, packed), slots, well_formed

        return self._traced_verify(n, "expanded", prepare, self._launch)

    def _traced_verify(self, n, backend, prepare, launch) -> np.ndarray:
        """Shared span choreography for both verify forms: one
        crypto.verify parent with pack (host prep) / dispatch (launch
        enqueue) / device_exec (wait-until-ready) / readback (D2H
        copy) children — the stage vocabulary BENCH's stage_breakdown
        and /debug/trace report. `prepare` returns (launch_args,
        slots, well_formed), slots None for a launch that gathers
        (its verdicts are the first n lanes);
        `launch(*launch_args, in_order)` returns the device verdict
        array. One launch-ledger record per call, its stages timed
        around the same blocks the spans bracket."""
        from ...libs.metrics import tpu_metrics

        if not self.sharded:
            # the sharded path observes occupancy in _route, against
            # the per-device routed bucket it actually executes
            tpu_metrics().batch_occupancy.observe(n / self._bucket(n))
        t = tracing.TRACER
        kernel = backend + ("_sharded" if self.sharded else "")
        with _ledger.launch(kernel) as rec, \
                t.span(tracing.CRYPTO_VERIFY, lanes=n, backend=backend):
            rec.lanes = n
            with rec.stage("pack"), t.span(tracing.CRYPTO_PACK, lanes=n):
                launch_args, slots, well_formed = prepare()
            rec.rows = "gathered" if slots is None else "in_order"
            rec.bytes_h2d = _ledger.nbytes_of(launch_args)
            with rec.stage("dispatch"), \
                    t.span(tracing.CRYPTO_DISPATCH, lanes=n):
                out = launch(*launch_args, slots is not None)
            if hasattr(out, "block_until_ready"):
                with rec.stage("exec"), \
                        t.span(tracing.CRYPTO_DEVICE_EXEC, lanes=n):
                    out.block_until_ready()
            with rec.stage("readback"), \
                    t.span(tracing.CRYPTO_READBACK, lanes=n):
                full = np.asarray(out)
            rec.result(out)
            rec.capacity = int(full.shape[0])
            rec.bytes_d2h = int(full.nbytes)
            if self.sharded:
                rec.n_devices = self.n_shards
                rec.active_devices = [
                    str(d) for d in self.mesh.devices.flat]
            res = (full[:n] if slots is None else full[slots]) \
                & well_formed
            rec.verdicts(res)
            return res

    # -- structured commit path (message bytes assembled on device) --

    # Message-buffer widths (bytes after the 64-byte R||A prefix) the
    # structured kernel compiles for: 2- and 4-block SHA inputs. Every
    # realistic vote fits in 192 (mlen <= 175); 448 covers pathological
    # chain-id/block-id combinations up to the guard below.
    _S_WIDTHS = (192, 448)
    # Template groups per launch, padded to a constant so every batch
    # shares one compiled shape: a single commit uses 1-2 groups
    # (for-block vs nil votes); a fast-sync window batches one group
    # per block's commit (BATCH_WINDOW); a vote micro-batch one per
    # distinct (type, height, round, block_id). Builders enforce the
    # same cap (types/sign_batch.py MAX_GROUPS) at construction so
    # overflow falls back to full bytes at the call site.
    _S_GROUPS = 32

    def _prepare_structured(self, indices, sbatch, sigs, lanes=None):
        n = len(indices)
        assert len(sbatch) == n
        idx = self._check_idx(indices, len(sigs))
        # Cheap host self-check: the structured reassembly of lane 0
        # must equal the independently-computed canonical sign bytes.
        # Catches template-math drift at the call site instead of
        # verifying wrong bytes.
        if sbatch.host_assemble(0) != sbatch.anchor_bytes():
            raise ValueError("structured sign-bytes self-check failed")
        max_len = sbatch.max_msg_len()
        width = next((w for w in self._S_WIDTHS if max_len <= w - 17),
                     None)
        if width is None:
            raise ValueError("sign bytes too long for structured path")
        # Fixed template shapes -> one compile per (width, bucket):
        # K padded to _S_GROUPS, pre to 128 B, suf to 64 B (every
        # legal vote fits; the guard keeps pathological inputs off
        # this path).
        k, pw = sbatch.pre.shape
        sw = sbatch.suf.shape[1]
        kp = self._S_GROUPS
        if k > kp or pw > 128 or sw > 64:
            raise ValueError("templates too large for structured path")
        # sharded tables: no pre-pad — _route buckets per device
        bucket = n if self.sharded else self._bucket(n)
        if lanes is not None and not self.sharded:
            # the live consensus path: one lane count, one width
            if n > lanes or width != self._S_WIDTHS[0]:
                raise ValueError("batch does not fit the live program")
            bucket = lanes

        def padded(a, rows):
            return np.pad(a, ((0, rows),) + ((0, 0),) * (a.ndim - 1))

        templates = dict(
            pre=np.pad(sbatch.pre, ((0, kp - k), (0, 128 - pw))),
            pre_len=padded(sbatch.pre_len, kp - k),
            suf=np.pad(sbatch.suf, ((0, kp - k), (0, 64 - sw))),
            suf_len=padded(sbatch.suf_len, kp - k))
        per_lane = dict(patch=sbatch.patch, split=sbatch.split,
                        patch_len=sbatch.patch_len, group=sbatch.group)
        base = self._in_order_base(idx, bucket)
        if base is not None:
            sig_raw, well_formed = self._sig_rows(sigs, 0)
            idx, fields, slots = self._in_slots(
                idx, base, bucket,
                dict(sb=sig_raw, s_ok=tv.s_range_ok(sig_raw), **per_lane))
            return idx, {**fields, **templates}, well_formed, width, slots
        pad = bucket - n
        sig_raw, well_formed = self._sig_rows(sigs, pad)
        if pad:
            idx = np.concatenate([idx, np.zeros(pad, np.int32)])
        fields = dict(
            sb=sig_raw, s_ok=tv.s_range_ok(sig_raw), **templates,
            **{name: padded(a, pad) for name, a in per_lane.items()})
        return idx, fields, well_formed, width, None

    _S_REPL = ("pre", "pre_len", "suf", "suf_len")

    def _launch_structured(self, idx, fields, width, in_order=False):
        if self.sharded:
            import jax

            per = {k: v for k, v in fields.items()
                   if k not in self._S_REPL}
            lidx, routed, btab, repl_s, slot = self._route(idx, per)
            _count_compile("structured_sharded",
                           (self.n_shards, lidx.shape[1], width))
            repl = {k: jax.device_put(fields[k], repl_s)
                    for k in self._S_REPL}
            with _phase_names_in_key():
                out = _skernel_sharded(WINDOWS_PER_ITER)(
                    idx=lidx,
                    akeys=self.akeys,
                    key_ok=self.key_ok,
                    atab=self.tables,
                    btab=btab,
                    width=width,
                    **routed,
                    **repl,
                )
            return _RoutedVerdicts(out, slot)
        idx, fields, btab = self._shard_args(
            idx, fields, repl_keys=self._S_REPL)
        _count_compile("structured",
                       (idx.shape[0], width) + _ROWS_KEY[in_order])
        with _phase_names_in_key():
            return _skernel(WINDOWS_PER_ITER)(
                idx=idx, akeys=self.akeys, key_ok=self.key_ok,
                atab=self.tables, btab=btab, width=width,
                in_order=in_order, **fields)

    def _structured_avals(self, bucket: int) -> dict:
        """What _launch_structured hands the program for `bucket`
        lanes (the shape after _shard_args' padding), as shapes, dtypes
        and placements alone: _prepare_structured's layout (templates
        padded to _S_GROUPS x 128 / 64 B, types/sign_batch.py's
        per-lane fields) without a batch to prepare. The same for
        either table read."""
        import jax

        from ...types.sign_batch import PATCH_W

        kp = self._S_GROUPS
        lane = dict(idx=((), np.int32), sb=((64,), np.uint8),
                    s_ok=((), np.bool_), patch=((PATCH_W,), np.uint8),
                    split=((), np.int32), patch_len=((), np.int32),
                    group=((), np.int32))
        templ = dict(pre=((128,), np.uint8), pre_len=((), np.int32),
                     suf=((64,), np.uint8), suf_len=((), np.int32))
        lane_s = repl_s = None
        if self.mesh is not None and bucket >= tv._SHARD_MIN:
            _row_s, lane_s, repl_s = tv._shardings(self.mesh)
        btab = tv.b_comb_tables()
        avals = {k: jax.ShapeDtypeStruct((bucket,) + tail, dt,
                                         sharding=lane_s)
                 for k, (tail, dt) in lane.items()}
        avals.update({k: jax.ShapeDtypeStruct((kp,) + tail, dt,
                                              sharding=repl_s)
                      for k, (tail, dt) in templ.items()})
        avals.update(
            akeys=_aval(self.akeys), key_ok=_aval(self.key_ok),
            atab=_aval(self.tables),
            btab=jax.ShapeDtypeStruct(btab.shape, btab.dtype,
                                      sharding=repl_s))
        return avals

    def verify_structured(self, indices, sbatch, sigs,
                          lanes: int | None = None) -> np.ndarray:
        """verify() for commit votes in structured form: identical
        verdicts to verify(indices, sbatch.materialize(), sigs), but
        the device assembles the sign bytes from the commit-wide
        template + per-lane timestamp patch (types/sign_batch.py), so
        the launch ships ~100 B/lane instead of ~330 B/lane. `lanes`:
        launch at exactly that many lanes, the batch padded up to them,
        in place of _bucket's shape (the live consensus path,
        ValidatorSet.verify_live: the one shape load_structured has
        loaded); ValueError if the batch does not fit it. Key-range-
        sharded tables bucket per device as ever."""
        n = len(indices)
        if n == 0:
            return np.zeros(0, bool)
        self._maybe_reshard()

        def prepare():
            idx, fields, well_formed, width, slots = \
                self._prepare_structured(indices, sbatch, sigs, lanes)
            return (idx, fields, width), slots, well_formed

        return self._traced_verify(n, "structured", prepare,
                                   self._launch_structured)


    def load_structured(self, lanes: int) -> int:
        """Compile (or load from the compile cache) and run once the
        structured programs at `lanes` lanes and the narrow width,
        over these tables and lanes that verify nothing: what
        verify_structured(lanes=...) launches from then on, the
        gathering program and, where a launch of so many lanes can be
        in order (_in_order_base), the one that reads the rows as
        they lie. Returns the programs loaded, 0 where all were
        loaded already."""
        from ...types.sign_batch import PATCH_W

        width = self._S_WIDTHS[0]
        if self.sharded:
            return 0
        _LIVE_LANES.add(lanes)
        kp = self._S_GROUPS
        zeros = np.zeros
        fields = dict(
            sb=zeros((lanes, 64), np.uint8), s_ok=zeros(lanes, np.bool_),
            pre=zeros((kp, 128), np.uint8), pre_len=zeros(kp, np.int32),
            suf=zeros((kp, 64), np.uint8), suf_len=zeros(kp, np.int32),
            patch=zeros((lanes, PATCH_W), np.uint8),
            split=zeros(lanes, np.int32), patch_len=zeros(lanes, np.int32),
            group=zeros(lanes, np.int32))
        idx = zeros(lanes, np.int32)
        both = self._in_order_base(idx[:1], lanes) is not None
        loaded = 0
        for in_order in (False, True)[:1 + both]:
            known = ("structured", lanes, width) + _ROWS_KEY[in_order] \
                in tv._COMPILED_SHAPES
            self._launch_structured(idx, fields, width,
                                    in_order).block_until_ready()
            loaded += not known
        return loaded


# -- process-wide LRU of expanded sets (one active + one in transition) --

_CACHE: OrderedDict[bytes, ExpandedKeys] = OrderedDict()
_CACHE_MAX = 2
# _CACHE_LOCK guards only the dict (fast ops). Builds are serialized
# PER KEY via _BUILDS events: a background warm (warm_async) racing a
# commit verify must not build the same multi-GB table twice — at 10k
# keys two concurrent builds' transients approach chip HBM — but a
# cache HIT for a different (already-built) valset must never wait
# behind another key's multi-second build.
_CACHE_LOCK = threading.Lock()
_BUILDS: dict[bytes, threading.Event] = {}
_WARM_THREAD = "expanded-warm"   # warm_async's thread, by name
# the lane counts load_structured has been asked for in this process
# (the live consensus path's): a set warmed after a validator-set
# change gets the same programs over ITS tables (their shape is part
# of the program) before its first vote arrives
_LIVE_LANES: set[int] = set()


def max_keys() -> int:
    """Largest valset the expanded tables serve on this backend.

    Accelerators: the single-chip table budget (_single_chip_max_keys:
    a share of the memory the device reports, ~39k keys on a 16 GB
    chip) times the mesh size — above the shard crossover
    the tables row-shard by key range across devices, so an N-chip
    mesh serves N × the single-chip cap. CPU backend (tests / e2e
    nets / degraded nodes): one build chunk regardless of the virtual
    mesh — the shards live inside ONE host RAM and there is no
    host->device wire to save, so big builds are pure cost. Callers
    fall back to the general batch path above the cap
    (ValidatorSet._use_expanded)."""
    import jax

    base = _single_chip_max_keys()
    if jax.devices()[0].platform == "cpu":
        return base  # virtual shards share one host RAM: no lift
    mesh = tv._mesh()
    return base * mesh.devices.size if mesh is not None else base


def key_digest(pubkeys: list[bytes]) -> bytes:
    """_CACHE's key for a set's keys, in validator order."""
    return hashlib.sha256(b"".join(pubkeys)).digest()


def get_expanded(pubkeys: list[bytes],
                 digest: bytes | None = None) -> ExpandedKeys:
    """The set's tables, from _CACHE or built now. `digest` is
    key_digest(pubkeys) where the caller holds it (a ValidatorSet
    hashes its keys once, not once a commit); hashed here otherwise."""
    from ...libs.metrics import tpu_metrics

    tmet = tpu_metrics()
    key = digest if digest is not None else key_digest(pubkeys)
    while True:
        with _CACHE_LOCK:
            exp = _CACHE.get(key)
            if exp is not None:
                _CACHE.move_to_end(key)
                tmet.expanded_cache.inc(event="hit")
                return exp
            ev = _BUILDS.get(key)
            if ev is None:
                ev = threading.Event()
                _BUILDS[key] = ev
                break  # this thread builds
        # Another thread is building this exact key: wait, then loop —
        # either the table is cached now, or the builder failed and
        # this thread claims the build itself.
        with tracing.TRACER.span(tracing.CRYPTO_TABLE_WAIT,
                                 keys=len(pubkeys)):
            ev.wait()
    warm = threading.current_thread().name == _WARM_THREAD
    with tracing.TRACER.span(
            tracing.CRYPTO_TABLE_BUILD, keys=len(pubkeys),
            thread="warm" if warm else "inline") as span:
        try:
            tmet.expanded_cache.inc(event="miss")
            with tmet.expanded_build_seconds.time():
                exp = ExpandedKeys(pubkeys)
            evicted = 0
            with _CACHE_LOCK:
                _CACHE[key] = exp
                while len(_CACHE) > _CACHE_MAX:
                    _CACHE.popitem(last=False)
                    evicted += 1
        finally:
            with _CACHE_LOCK:
                _BUILDS.pop(key, None)
            ev.set()
        span.set_attr("bytes", int(exp.tables.nbytes))
        span.set_attr("evicted", evicted)
        if tracing.TRACER.enabled:
            # the span ends when the tables are on the device, not when
            # their last builder launch is enqueued; the tables are in
            # _CACHE and the waiters released by now, as without a span
            import jax

            jax.block_until_ready(exp.tables)
    return exp


def structured_phases() -> dict[str, str]:
    """{instruction: ed25519.* phase} (tv.phase_of_instructions) of the
    structured program, at the shape this process compiled last and
    over the tables used last: for whoever lays a profiler trace's
    device operations on the kernels' phases. A trace names an
    operation by its optimized-HLO instruction and holds no scope. The
    names are read off the executable the launches run: structured
    launches are lowered and cached with their operations' names in
    the key (_phase_names_in_key), so even one loaded from the
    persistent cache was compiled from these very names. Lowering the
    program again from the launch's shapes and placements
    (_structured_avals) is answered by jit's own caches with that
    executable: nothing compiles and nothing is loaded. Nothing on the
    launch path keeps anything for it."""
    with _CACHE_LOCK:
        keys = next(reversed(_CACHE.values()), None)
    shape = next((k[1:] for k in reversed(tv._COMPILED_SHAPES)
                  if k[0] == "structured"), None)
    if keys is None or shape is None or keys.sharded:
        raise ValueError("no structured launch on one chip's tables "
                         "yet: nothing to map")
    bucket, width, *rows = shape
    with _phase_names_in_key():
        compiled = _skernel(WINDOWS_PER_ITER).lower(
            width=width, in_order=bool(rows),
            **keys._structured_avals(bucket)).compile()
    return tv.phase_of_instructions(compiled.as_text())


def warm_async(pubkeys: list[bytes]) -> threading.Thread:
    """Build (or touch) the expanded tables for a valset in a
    background thread, so the first commit verify after a validator
    -set change doesn't pay the multi-second table build inline.
    In consensus the NEXT valset is known two heights ahead
    (state/execution.py update_state; reference state/execution.go:406)
    — exactly the window this hides the build in. Returns the thread
    (callers/tests may join; the node fires and forgets)."""

    def build():
        try:
            exp = get_expanded(pubkeys)
            for lanes in sorted(_LIVE_LANES):
                exp.load_structured(lanes)
        except Exception:  # pragma: no cover - depends on device state
            from .. import batch as _batch

            _batch.logger.exception(
                "background expanded-table warm failed (%d keys)",
                len(pubkeys))

    t = threading.Thread(target=build, name=_WARM_THREAD, daemon=True)
    t.start()
    return t
