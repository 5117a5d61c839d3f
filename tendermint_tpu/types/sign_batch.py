"""Structured sign-bytes: template + per-lane timestamp patch.

Within one commit — and across the votes of one (type, height, round,
block_id) — every canonical sign-byte blob shares all content except
the timestamp field and the outer length prefix (types/canonical.py
vote_sign_bytes; reference types/canonical.go). Shipping full
(N, ~190 B) sign-byte rows to the device per verify is therefore
~90% redundant — the dominant host->device transfer term — and
building them costs one Python protobuf Writer per lane.

The structured batches here capture the structure instead:

  sign_bytes[lane] = outer_varint ‖ pre[group] ‖ ts_field ‖ suf[group]

with a handful of (pre, suf) template groups and a <=20-byte per-lane
patch = outer_varint ‖ ts_field built by vectorized numpy (no per-lane
Python). The device kernel (crypto/tpu/expanded.py structured
front-end) reassembles the exact bytes on device; `materialize()`
yields the identical full bytes for host/fallback paths, and tests
enforce byte equality between the two.

A commit's slots reach all of this as columns: CommitColumns reads the
CommitSig objects ONCE, by C-level iteration, and the verify sites
(types/validator_set.py, blockchain/verify_ahead.py) and
CommitSignBatch compute lanes, signatures, tally, address check,
template groups and timestamps from the columns with array operations.

Shapes:
  CommitSignBatch — one commit's slots (groups: for-block vs nil).
  MergedSignBatch — a fast-sync window: several commits, one group
                    per commit (blockchain/reactor.py).
  VoteSignBatch   — a live gossip vote micro-batch: one group per
                    distinct (type, height, round, block_id)
                    (consensus/state.py vote scheduler).
"""

from __future__ import annotations

import threading
from dataclasses import InitVar, dataclass, field
from itertools import compress
from operator import attrgetter, ne
from typing import NamedTuple

import numpy as np

from ..crypto import tmhash
from . import canonical
from .block import BlockIDFlag

PATCH_W = 24  # outer varint (<=2) + ts field (<=18), zero-padded

# Template groups the device kernel accepts per launch
# (crypto/tpu/expanded.py pads to exactly this many rows). Builders
# raise ValueError past it so call sites fall back to full bytes
# SILENTLY — overflow is an input property (e.g. a peer fabricating
# many distinct block_ids in one gossip burst), not a template bug.
MAX_GROUPS = 32


# v >= _VARINT_EDGES[k] needs more than k varint bytes (int64 >= 0:
# at most nine).
_VARINT_EDGES = np.array([1 << (7 * k) for k in range(9)], np.int64)


def _vlen(v: np.ndarray) -> np.ndarray:
    """Minimal varint byte length per element of v >= 0; 0 where the
    value is 0 (the field is then absent from the encoding)."""
    return np.searchsorted(_VARINT_EDGES, v, side="right")


def _varint_digits(out: np.ndarray, rows, col: int, v: np.ndarray,
                   width: int) -> int:
    """Write the minimal varint of each v into out[rows, col:]:
    `width` is the byte length of the longest, and a shorter one
    leaves zeros after its last byte. Returns the column after."""
    for j in range(width):
        rest = v >> 7
        out[rows, col + j] = (v & 0x7F) | (rest > 0) * 0x80
        v = rest
    return col + width


def _pack_templates(parts: list[tuple[bytes, bytes]]):
    """(pre, suf) template list -> padded arrays + lengths."""
    k = max(len(parts), 1)
    if not parts:
        parts = [(b"", b"")]
    pw = max(max(len(p) for p, _ in parts), 1)
    sw = max(max(len(s) for _, s in parts), 1)
    pre = np.zeros((k, pw), np.uint8)
    suf = np.zeros((k, sw), np.uint8)
    pre_len = np.zeros(k, np.int32)
    suf_len = np.zeros(k, np.int32)
    for g, (p, s) in enumerate(parts):
        pre[g, :len(p)] = np.frombuffer(p, np.uint8)
        suf[g, :len(s)] = np.frombuffer(s, np.uint8)
        pre_len[g] = len(p)
        suf_len[g] = len(s)
    return pre, pre_len, suf, suf_len


def _build_patches(pre_len, suf_len, group, ts):
    """Vectorized outer-varint + ts-field assembly.

    A lane's patch is outer ‖ 0x2A pay ‖ 0x08 secs ‖ 0x10 nanos, each
    piece absent when its value is 0. The nanos come last, so lanes
    whose nanos differ in width still share their columns
    (_varint_digits leaves zeros after a shorter one); only the outer
    varint's length and the seconds' width move a byte's column, and
    within one batch those take one value or two (the seconds share a
    varint width), so each column is written over all rows at once.

    Returns (patch, split, patch_len); raises ValueError when a blob
    would exceed the two-byte outer-varint range."""
    n = ts.shape[0]
    secs, nanos = np.divmod(ts, 1_000_000_000)
    ls = _vlen(secs)
    ln = _vlen(nanos)
    pay = ls + (ls > 0) + ln + (ln > 0)
    tsf_total = pay + 2 * (pay > 0)
    body = (pre_len.astype(np.int64) + suf_len)[group] + tsf_total
    if body.size and body.max() >= 1 << 14:
        raise ValueError("sign bytes too long for structured batch")
    outer_len = 1 + (body >= 128)

    patch = np.zeros((n, PATCH_W), np.uint8)
    split = outer_len.astype(np.int32)
    patch_len = (outer_len + tsf_total).astype(np.int32)
    # layout key: what fixes the columns of every piece but the last
    key = outer_len * 16 + ls
    keys = np.flatnonzero(np.bincount(key, minlength=48))
    for kv in keys.tolist():
        rows = slice(None) if len(keys) == 1 else np.flatnonzero(key == kv)
        ol, width_s = divmod(kv, 16)
        bd = body[rows]
        if ol == 1:
            patch[rows, 0] = bd
        else:
            patch[rows, 0] = (bd & 0x7F) | 0x80
            patch[rows, 1] = bd >> 7
        py = pay[rows]
        patch[rows, ol] = (py > 0) * 0x2A  # field 5, wire type 2
        patch[rows, ol + 1] = py
        col = ol + 2
        if width_s:
            patch[rows, col] = 0x08
            col = _varint_digits(patch, rows, col + 1, secs[rows], width_s)
        lnr = ln[rows]
        patch[rows, col] = (lnr > 0) * 0x10
        _varint_digits(patch, rows, col + 1, nanos[rows],
                       int(lnr.max(initial=0)))
    return patch, split, patch_len


def _check_ts(ts: int) -> int:
    if not 0 <= ts < 1 << 63:
        # Vectorized path is int64; a (hostile) timestamp past year
        # 2262 falls back to the full-bytes path instead.
        raise ValueError("timestamp out of int64 range")
    return ts


_FLAG = attrgetter("block_id_flag")
_ADDRESS = attrgetter("validator_address")
_TIMESTAMP = attrgetter("timestamp")
_SIGNATURE = attrgetter("signature")


class CommitColumns:
    """One Commit's CommitSig slots as columns, each read by ONE
    C-level pass over the slots (map + attrgetter), for the verify
    sites and CommitSignBatch to compute on with array operations.
    Where a value fits no column (a flag past a byte, a timestamp past
    int64, an address of another length) that slot is looked at by
    itself, here. Nothing of it is kept on the Commit: CommitSig is
    mutable, so a Commit is read again each time it is verified."""

    __slots__ = ("commit", "present", "for_block")

    def __init__(self, commit):
        self.commit = commit
        try:
            flags = bytes(map(_FLAG, commit.signatures))
        except ValueError:
            # no BlockIDFlag is past a byte: such a slot is neither
            # absent nor for the block
            flags = bytes(f if 0 <= f < 256 else 0
                          for f in map(_FLAG, commit.signatures))
        flags = np.frombuffer(flags, np.uint8)
        # (n,) bool each: not is_absent(), for_block()
        self.present = flags != BlockIDFlag.ABSENT
        self.for_block = flags == BlockIDFlag.COMMIT

    def signatures(self, mask: np.ndarray) -> list[bytes]:
        """The signatures of the slots a bool mask selects, in slot
        order; a mask shorter than the commit selects among its first
        slots only."""
        return list(compress(map(_SIGNATURE, self.commit.signatures),
                             mask.tobytes()))

    def wrong_address(self, addresses: list[bytes]) -> int | None:
        """The lowest present slot whose non-empty address differs
        from its validator's (addresses[slot]), or None."""
        sigs = self.commit.signatures
        # every slot that differs, absent ones with their empty
        # address among them: few, and looked at one by one
        for idx in compress(range(len(sigs)),
                            map(ne, map(_ADDRESS, sigs), addresses)):
            if self.present[idx] and sigs[idx].validator_address:
                return idx
        return None

    def timestamps(self, slots: np.ndarray) -> np.ndarray:
        """(len(slots),) int64 timestamps of the given slots;
        ValueError when one of THOSE is outside [0, 2^63) — the
        vectorized layout is int64, and structured_or_bytes turns that
        into the full-bytes path."""
        sigs = self.commit.signatures
        try:
            ts = np.fromiter(map(_TIMESTAMP, sigs), np.int64,
                             len(sigs))[slots]
        except OverflowError:
            ts = None  # some slot's value fits no int64: maybe not ours
        if ts is None or (ts.size and ts.min() < 0):
            ts = np.array([_check_ts(sigs[s].timestamp) for s in slots],
                          np.int64)
        return ts


class CommitSigRows(NamedTuple):
    """A Commit's slots as they stand on the wire: `wire` is every
    slot as field 4 of its Commit (22 len body), one after another;
    `ends` is where each slot's row ends in it."""

    wire: bytes
    ends: np.ndarray  # (n,) intp

    def leaves(self) -> list[bytes]:
        """Each slot's CommitSig proto by itself (the leaves of
        Commit.hash()): its row less the tag and the length, one byte
        each since no slot that fits the columns reaches 128 bytes."""
        ends = self.ends.tolist()
        wire = self.wire
        return [wire[s + 2:e] for s, e in zip([0] + ends, ends)]


# commits whose slots this thread's commit_sig_rows encoded; a caller
# that wants to know of its own encodes subtracts two readings
# (store.encode_commits attr `columnar`). Per thread: a fast-sync
# window job builds part sets beside the apply loop.
_ENCODED = threading.local()


def columnar_encodes() -> int:
    return getattr(_ENCODED, "n", 0)


_U8 = np.uint8
_ADDR_W = tmhash.TRUNCATED_SIZE
_SIG_W = 64  # SIGNATURE_SIZE of ed25519, sr25519 and secp256k1 alike


def commit_sig_rows(commit) -> CommitSigRows | None:
    """CommitSig.to_proto() of every slot, by array operations: what
    `for cs in signatures: w.message(4, cs.to_proto())` writes, byte
    for byte, or None where a slot fits no column (a flag past a byte,
    a timestamp outside [0, 2^63), a present slot whose address is not
    20 bytes or whose signature is not 64, an absent slot that carries
    anything): the per-slot writer then encodes that commit. Nothing
    is kept: CommitSig is mutable, so every encode reads the slots.

    A slot's row is five pieces, each of fixed columns but for ONE
    varint at its end (_varint_digits leaves zeros after a shorter
    one), so every column is written over all rows at once and one
    mask cuts each piece to its length:

      22 len 08 flag | 12 14 addr[20] | 1a len 08 secs | 10 nanos
      | 22 40 sig[64]
    """
    sigs = commit.signatures
    n = len(sigs)
    addrs = list(map(_ADDRESS, sigs))
    sgs = list(map(_SIGNATURE, sigs))
    try:
        # a flag or a length past a byte is a ValueError here
        flags, alen, slen = (
            np.frombuffer(bytes(column), _U8) for column in
            (map(_FLAG, sigs), map(len, addrs), map(len, sgs)))
        ts = np.fromiter(map(_TIMESTAMP, sigs), np.int64, n)
    except (ValueError, OverflowError, TypeError):
        return None
    present = flags != BlockIDFlag.ABSENT
    if ((alen != present * _U8(_ADDR_W)).any()
            or (slen != present * _U8(_SIG_W)).any()
            or (ts < 0).any() or ts[~present].any()):
        return None
    secs, nanos = np.divmod(ts, 1_000_000_000)
    # lengths are bytes throughout (a row is under 128 bytes): the
    # mask below compares a hundred columns a row
    lf, ls, ln = (_vlen(v).astype(_U8) for v in (flags, secs, nanos))
    wf, ws, wn = (int(v.max(initial=0)) for v in (lf, ls, ln))
    pay = ls + (ls > 0) + ln + (ln > 0)
    # each piece's width, and each row's length within it
    widths = (3 + wf, 2 + _ADDR_W, 3 + ws, 1 + wn, 2 + _SIG_W)
    lens = (lf + (lf > 0) + _U8(2), present * _U8(2 + _ADDR_W),
            ls + (ls > 0) + (pay > 0) * _U8(2), ln + (ln > 0),
            present * _U8(2 + _SIG_W))
    row_len = sum(lens[1:], lens[0])
    c1, c2, c3, c4, width = np.cumsum(widths).tolist()
    m = np.zeros((n, width), _U8)
    every = slice(None)
    m[:, 0] = 0x22
    m[:, 1] = row_len - _U8(2)
    m[:, 2] = 0x08
    _varint_digits(m, every, 3, flags, wf)
    m[:, c1] = 0x12
    m[:, c1 + 1] = _ADDR_W
    m[present, c1 + 2:c2] = np.frombuffer(
        b"".join(addrs), _U8).reshape(-1, _ADDR_W)
    m[:, c2] = 0x1A
    m[:, c2 + 1] = pay
    m[:, c2 + 2] = 0x08
    _varint_digits(m, every, c2 + 3, secs, ws)
    m[:, c3] = 0x10
    _varint_digits(m, every, c3 + 1, nanos, wn)
    m[:, c4] = 0x22
    m[:, c4 + 1] = _SIG_W
    m[present, c4 + 2:] = np.frombuffer(
        b"".join(sgs), _U8).reshape(-1, _SIG_W)
    col = np.concatenate([np.arange(w, dtype=_U8) for w in widths])
    keep = col < np.repeat(np.stack(lens, axis=1), widths, axis=1)
    _ENCODED.n = columnar_encodes() + 1
    return CommitSigRows(m[keep].tobytes(),
                         np.cumsum(row_len, dtype=np.intp))


class StructuredSignBytes:
    """Base for structured sign-byte batches: the field layout the
    device kernel front-end consumes (pre/suf templates + per-lane
    group/patch/split/patch_len) plus the host-side reassembly the
    self-check and width selection need. ValidatorSet's batch verify
    dispatches on this type."""

    def _finish(self, parts, group, ts):
        self.pre, self.pre_len, self.suf, self.suf_len = \
            _pack_templates(parts)
        self.group = group
        self.patch, self.split, self.patch_len = _build_patches(
            self.pre_len, self.suf_len, group, ts)

    def host_assemble(self, i: int) -> bytes:
        """Reassemble lane i's sign bytes host-side with the SAME
        boundary math the device kernel uses — the runtime self-check
        anchor (compared against anchor_bytes()/materialize())."""
        g = int(self.group[i])
        a = int(self.split[i])
        pl = int(self.patch_len[i])
        return (bytes(self.patch[i, :a])
                + bytes(self.pre[g, :self.pre_len[g]])
                + bytes(self.patch[i, a:pl])
                + bytes(self.suf[g, :self.suf_len[g]]))

    def anchor_bytes(self) -> bytes:
        """Lane 0's canonical sign bytes, computed INDEPENDENTLY of
        the structured arrays — the runtime self-check compares
        host_assemble(0) against this before any launch."""
        raise NotImplementedError

    def msg_lens(self) -> np.ndarray:
        """Per-lane total sign-byte length (outer prefix included)."""
        return (self.patch_len + self.pre_len[self.group]
                + self.suf_len[self.group]).astype(np.int64)

    def max_msg_len(self) -> int:
        return int(self.msg_lens().max()) if len(self) else 0


@dataclass
class CommitSignBatch(StructuredSignBytes):
    """Sign bytes for a list of commit slots, in structured form."""

    chain_id: str
    commit: object
    slots: "list[int] | np.ndarray"
    # the commit's columns where the caller has read them already;
    # used here and not kept
    columns: InitVar[CommitColumns | None] = None
    # templates, one row per group
    pre: np.ndarray = field(init=False)       # (K, PW) uint8
    pre_len: np.ndarray = field(init=False)   # (K,) int32
    suf: np.ndarray = field(init=False)       # (K, SW) uint8
    suf_len: np.ndarray = field(init=False)   # (K,) int32
    # per-lane
    group: np.ndarray = field(init=False)     # (N,) int32
    patch: np.ndarray = field(init=False)     # (N, PATCH_W) uint8
    split: np.ndarray = field(init=False)     # (N,) int32 outer-varint len
    patch_len: np.ndarray = field(init=False)  # (N,) int32

    def __post_init__(self, columns):
        from .vote import VoteType

        commit = self.commit
        if columns is None:
            columns = CommitColumns(commit)
        slots = np.asarray(self.slots, np.intp)
        ts = columns.timestamps(slots)
        # group ids in order of first appearance, keyed by for_block()
        fb = columns.for_block[slots]
        group = (fb != fb[:1]).astype(np.int32)
        firsts = [0][:len(slots)]
        if group.any():
            firsts.append(int(group.argmax()))
        parts = [
            canonical.vote_sign_parts(
                self.chain_id, int(VoteType.PRECOMMIT), commit.height,
                commit.round,
                commit.signatures[slots[i]].block_id_for(commit.block_id))
            for i in firsts]
        self._finish(parts, group, ts)

    def __len__(self) -> int:
        return len(self.slots)

    def anchor_bytes(self) -> bytes:
        return self.commit.vote_sign_bytes(self.chain_id, self.slots[0])

    def materialize(self) -> list[bytes]:
        """Full canonical sign bytes per lane (host/fallback path)."""
        return [self.commit.vote_sign_bytes(self.chain_id, s)
                for s in self.slots]


class MergedSignBatch(StructuredSignBytes):
    """Several commits' CommitSignBatches as ONE structured batch —
    the fast-sync window shape (blockchain/reactor.py): a window of
    consecutive blocks, all signed by the same validator set, verifies
    in a single device launch with one template group per commit.
    Field layout is identical to CommitSignBatch (the kernel front-end
    treats both the same); group ids are offset per sub-batch."""

    def __init__(self, batches: list[CommitSignBatch]):
        assert batches
        if sum(b.pre.shape[0] for b in batches) > MAX_GROUPS:
            raise ValueError("too many commit groups for one "
                             "structured launch")
        self.batches = batches
        pw = max(b.pre.shape[1] for b in batches)
        sw = max(b.suf.shape[1] for b in batches)
        pres, sufs, groups = [], [], []
        off = 0
        for b in batches:
            k = b.pre.shape[0]
            pres.append(np.pad(b.pre, ((0, 0), (0, pw - b.pre.shape[1]))))
            sufs.append(np.pad(b.suf, ((0, 0), (0, sw - b.suf.shape[1]))))
            groups.append(b.group + off)
            off += k
        self.pre = np.concatenate(pres, axis=0)
        self.suf = np.concatenate(sufs, axis=0)
        self.pre_len = np.concatenate([b.pre_len for b in batches])
        self.suf_len = np.concatenate([b.suf_len for b in batches])
        self.group = np.concatenate(groups)
        self.patch = np.concatenate([b.patch for b in batches], axis=0)
        self.split = np.concatenate([b.split for b in batches])
        self.patch_len = np.concatenate([b.patch_len for b in batches])

    def __len__(self) -> int:
        return int(self.group.shape[0])

    def anchor_bytes(self) -> bytes:
        return self.batches[0].anchor_bytes()

    def materialize(self) -> list[bytes]:
        out: list[bytes] = []
        for b in self.batches:
            out.extend(b.materialize())
        return out


class VoteSignBatch(StructuredSignBytes):
    """A live gossip vote micro-batch (consensus/state.py scheduler)
    in structured form: one template group per distinct
    (type, height, round, block_id) — during one round's burst that is
    1-2 groups for thousands of votes, so the launch ships per-lane
    timestamp patches instead of full sign-byte rows, exactly like the
    commit path."""

    def __init__(self, chain_id: str, votes: list):
        self.chain_id = chain_id
        self.votes = votes
        n = len(votes)
        parts: list[tuple[bytes, bytes]] = []
        group_of: dict = {}
        group = np.zeros(n, np.int32)
        ts = np.zeros(n, np.int64)
        for i, v in enumerate(votes):
            ts[i] = _check_ts(v.timestamp)
            key = (int(v.type), v.height, v.round, v.block_id)
            g = group_of.get(key)
            if g is None:
                if len(parts) >= MAX_GROUPS:
                    raise ValueError("too many vote groups for one "
                                     "structured launch")
                g = len(parts)
                group_of[key] = g
                parts.append(canonical.vote_sign_parts(
                    chain_id, int(v.type), v.height, v.round,
                    v.block_id))
            group[i] = g
        self._finish(parts, group, ts)

    def __len__(self) -> int:
        return len(self.votes)

    def anchor_bytes(self) -> bytes:
        return self.votes[0].sign_bytes(self.chain_id)

    def materialize(self) -> list[bytes]:
        return [v.sign_bytes(self.chain_id) for v in self.votes]
