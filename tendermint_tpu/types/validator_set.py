"""ValidatorSet: proposer rotation + batched commit verification
(reference: types/validator_set.go).

The verify_commit* family is the framework's north-star surface: where
the reference loops `PubKey.VerifySignature` per signature
(validator_set.go:683-705,720-762,776-824), every variant here collects
its exact verification set first and executes it as ONE device batch
with per-lane verdicts. The ed25519 keys of a large set additionally
route through crypto/tpu/expanded.py: per-validator comb tables cached
on device across heights (the valset persists block to block), which
removes pubkey decompression and all scalar-mul doublings from the
per-commit critical path. A set that holds other key types beside them
is split by key type at the verify site (_batch_verify_lanes): its
ed25519 lanes ride the tables of its ed25519 keys, its sr25519 lanes
one launch of their own kernel, verdicts back in the caller's order."""

from __future__ import annotations

import dataclasses
import hashlib
from operator import attrgetter

import numpy as np

from ..crypto import merkle
from ..crypto.batch import BatchVerifier
from ..libs import tracing
from ..libs.tracing import TRACER
from .block import BlockID
from .sign_batch import CommitColumns, CommitSignBatch, StructuredSignBytes
from .validator import Validator

MAX_TOTAL_VOTING_POWER = (1 << 62) // 8
PRIORITY_WINDOW_SIZE_FACTOR = 2
# Lanes at/above this go through the expanded per-validator comb
# tables (crypto/tpu/expanded.py MIN_EXPAND); below it the general
# batch kernel / host path wins because the table build + HBM
# residency don't amortize.
_EXPAND_MIN = 128
# key types the lane split tells apart (_SetColumns.kind_code)
_KIND_ED25519, _KIND_SR25519, _KIND_OTHER = 0, 1, 2
_KIND_CODES = {"ed25519": _KIND_ED25519, "sr25519": _KIND_SR25519}


class VerificationError(Exception):
    pass


class CommitVerifyPlan:
    """One commit-check decomposed into its signature lanes BEFORE any
    cryptography runs: the selection loops of verify_commit_light /
    verify_commit_light_trusting (power tally, address matching, the
    insufficient-power rejections) produce a plan, and the signature
    work is a separate step. The split lets the light serving plane
    (light/serving.py) coalesce the lanes of MANY independent plans —
    concurrent client requests, both checks of one skipping step —
    into a single wide device launch, while the classic verify_commit*
    methods just plan + execute inline."""

    __slots__ = ("valset", "lanes", "slots", "sigs", "msgs", "form")

    def __init__(self, valset: "ValidatorSet", lanes: list[int],
                 slots: list[int], sigs: list[bytes], msgs, form: str):
        self.valset = valset
        self.lanes = lanes    # indices into valset.validators (tables)
        self.slots = slots    # commit signature slots (error reports)
        self.sigs = sigs
        self.msgs = msgs      # list[bytes] | StructuredSignBytes
        self.form = form      # light | trusting (the span's attr)

    def __len__(self) -> int:
        return len(self.lanes)

    def triples(self) -> list[tuple]:
        """(pub_key, sign_bytes, signature) per lane, msgs
        materialized — the form a cross-plan batch consumes (different
        plans may come from different validator sets, so the shared
        launch uses the general per-lane-key kernel, not this set's
        expanded tables)."""
        msgs = self.msgs if isinstance(self.msgs, list) \
            else self.msgs.materialize()
        return [(self.valset.validators[i].pub_key, m, s)
                for i, m, s in zip(self.lanes, msgs, self.sigs)]

    def raise_invalid(self, verdicts) -> None:
        """Map per-lane verdicts back to commit slots; raise the same
        VerificationError the inline verify_commit* paths produce."""
        bad = [self.slots[i] for i in range(len(self.slots))
               if not verdicts[i]]
        if bad:
            raise VerificationError(
                f"invalid signature(s) at index(es) {bad}")

    def execute(self) -> None:
        """Verify this plan alone (the classic inline path): one
        batch through the owning set's expanded tables / BatchVerifier."""
        with TRACER.span(tracing.VERIFY_COMMIT, form=self.form,
                         lanes=len(self.lanes),
                         structured=_is_structured(self.msgs)):
            ok, verdicts = self.valset._batch_verify_lanes(
                self.lanes, self.msgs, self.sigs)
            if not ok:
                self.raise_invalid(verdicts)


def _is_structured(msgs) -> bool:
    """Do these sign bytes — of a SplitSignBytes, its ed25519 lanes' —
    ride in structured form?"""
    if isinstance(msgs, SplitSignBytes):
        msgs = msgs.ed_msgs
    return isinstance(msgs, StructuredSignBytes)


class SplitSignBytes:
    """Sign bytes for lanes of more than one key type
    (structured_or_bytes, for a set that is not all ed25519): the
    ed25519 lanes' — structured where the tables will take them — and
    the other lanes' in full, each beside its positions in the
    caller's lane order."""

    __slots__ = ("ed_pos", "ed_msgs", "rest_pos", "rest_msgs")

    def __init__(self, ed_pos: np.ndarray, ed_msgs,
                 rest_pos: np.ndarray, rest_msgs: list[bytes]):
        self.ed_pos = ed_pos      # positions among the lanes, ascending
        self.ed_msgs = ed_msgs    # list[bytes] | StructuredSignBytes
        self.rest_pos = rest_pos
        self.rest_msgs = rest_msgs

    def materialize(self) -> list[bytes]:
        """Full sign bytes of every lane, in the caller's order."""
        ed = self.ed_msgs if isinstance(self.ed_msgs, list) \
            else self.ed_msgs.materialize()
        out = [b""] * (len(ed) + len(self.rest_msgs))
        for i, m in zip(self.ed_pos.tolist(), ed):
            out[i] = m
        for i, m in zip(self.rest_pos.tolist(), self.rest_msgs):
            out[i] = m
        return out


@dataclasses.dataclass
class _SetColumns:
    """What the verify sites need of a validator set, as columns:
    built once per set (ValidatorSet._columns), never per commit.
    `src` is the validators list they were read from — the validity
    key, as for _addr_index. `ed_keys` are the set's ed25519 keys in
    set order: what crypto/tpu/expanded.py's tables are built over and
    what `digest`, the key of its table cache, names (hashed on first
    use; the tables themselves stay in that cache, whose LRU alone
    decides when they leave the chip). For a set that is all ed25519
    they are `pubkeys` itself, a validator's row is its index, and
    `ed_row` / `kind_code` are None: nothing is looked up per lane."""

    src: list
    addresses: list[bytes]   # per validator, for the address check
    power: np.ndarray        # (n,) voting powers; see _power_column
    all_ed25519: bool
    pubkeys: list[bytes]
    ed_keys: list[bytes]
    ed_row: np.ndarray | None = None     # (n,) row in ed_keys; -1: none
    kind_code: np.ndarray | None = None  # (n,) _KIND_* of each key
    digest: bytes | None = None
    membership: bytes | None = None  # see ValidatorSet.membership_digest


def _power_column(validators: list) -> np.ndarray:
    """Voting powers as int64 wherever every tally of a subset is
    exact in it (no negative power, total within the cap of 2^59);
    Python ints in an object array for a set that breaks either, so
    that its tallies grow and do not wrap, until
    total_voting_power() raises."""
    powers = list(map(attrgetter("voting_power"), validators))
    exact = not powers or (
        min(powers) >= 0 and sum(powers) <= MAX_TOTAL_VOTING_POWER)
    return np.array(powers, np.int64 if exact else object)


class ValidatorSet:
    def __init__(self, validators: list[Validator]):
        self._total: int | None = None
        self._addr_cache: dict = {}
        self._addr_cache_src: list | None = None
        self._cols: _SetColumns | None = None
        if validators:
            vals = [v.copy() for v in validators]
            vals.sort(key=lambda v: (-v.voting_power, v.address))
            self.validators = vals
            self.proposer: Validator | None = None
            self._increment_proposer_priority(1)
        else:
            self.validators = []
            self.proposer = None

    # -- queries --

    def __len__(self) -> int:
        return len(self.validators)

    def total_voting_power(self) -> int:
        if self._total is None:
            self._total = sum(v.voting_power for v in self.validators)
            if self._total > MAX_TOTAL_VOTING_POWER:
                raise ValueError("total voting power exceeds cap")
        return self._total

    def _addr_index(self) -> dict:
        """address -> index map, rebuilt when the validators list is
        replaced or grows (callers outside this class assign/append to
        .validators directly, so validity is keyed on the list object
        + its length rather than on construction sites). Turns the
        per-conflicting-vote / per-evidence-item lookups — and
        update_with_change_set's has_address loop — from O(n) scans
        into O(1) at the 10k-validator design point (the reference
        keeps sorted order + binary search, validator_set.go:646)."""
        vals = self.validators
        if self._addr_cache_src is not vals or \
                len(self._addr_cache) != len(vals):
            self._addr_cache = {v.address: i for i, v in enumerate(vals)}
            self._addr_cache_src = vals
        return self._addr_cache

    def _held_columns(self) -> _SetColumns | None:
        """The columns if they still describe this set — _addr_index's
        rule: the validators list they were read from, at its length."""
        cols = self._cols
        if cols is not None and cols.src is self.validators and \
                len(cols.addresses) == len(cols.src):
            return cols
        return None

    def _columns(self) -> _SetColumns:
        cols = self._held_columns()
        if cols is None:
            vals = self.validators
            pubkeys = [v.pub_key.bytes() for v in vals]
            code = np.fromiter(
                (_KIND_CODES.get(v.pub_key.type_name, _KIND_OTHER)
                 for v in vals), np.uint8, len(vals))
            is_ed = code == _KIND_ED25519
            cols = _SetColumns(
                src=vals,
                addresses=[v.address for v in vals],
                power=_power_column(vals),
                all_ed25519=bool(is_ed.all()),
                pubkeys=pubkeys, ed_keys=pubkeys)
            if not cols.all_ed25519:
                cols.ed_keys = [pubkeys[i] for i in np.flatnonzero(is_ed)]
                cols.ed_row = np.where(is_ed, np.cumsum(is_ed) - 1, -1)
                cols.kind_code = code
            # held only once whole: the window's thread and the apply
            # loop read one set's columns side by side
            self._cols = cols
        return cols

    def membership_digest(self) -> bytes:
        """32 bytes that stand for the set's key types, keys, powers
        and order (what hash() commits to, without its Merkle tree),
        hashed once per set and held with its columns. The state store
        (state/store.py) names with it the membership a row holds."""
        cols = self._columns()
        if cols.membership is None:
            kinds = "ed25519" if cols.all_ed25519 else ",".join(
                v.pub_key.type_name for v in cols.src)
            cols.membership = hashlib.sha256(b"|".join((
                kinds.encode(), b"".join(cols.pubkeys),
                ",".join(map(str, cols.power.tolist())).encode(),
            ))).digest()
        return cols.membership

    def get_by_address(self, addr: bytes) -> tuple[int, Validator | None]:
        i = self._addr_index().get(addr, -1)
        return (i, self.validators[i]) if i >= 0 else (-1, None)

    def get_by_index(self, i: int) -> Validator | None:
        if 0 <= i < len(self.validators):
            return self.validators[i]
        return None

    def has_address(self, addr: bytes) -> bool:
        return self.get_by_address(addr)[0] >= 0

    def hash(self) -> bytes:
        return merkle.hash_from_byte_slices(
            [v.bytes_for_hash() for v in self.validators]
        )

    def copy(self) -> "ValidatorSet":
        vs = ValidatorSet([])
        vs.validators = [v.copy() for v in self.validators]
        if self.proposer is not None:
            i, _ = self.get_by_address(self.proposer.address)
            vs.proposer = vs.validators[i] if i >= 0 else self.proposer.copy()
        vs._total = self._total
        cols = self._held_columns()
        if cols is not None:
            # same keys, addresses and powers: the columns are values
            vs._cols = dataclasses.replace(cols, src=vs.validators)
        return vs

    def validate_basic(self) -> None:
        if not self.validators:
            raise ValueError("empty validator set")
        for v in self.validators:
            v.validate_basic()
        if self.proposer is None:
            raise ValueError("no proposer")

    # -- proposer rotation (reference: validator_set.go:110-230) --

    def increment_proposer_priority(self, times: int) -> None:
        if times <= 0:
            raise ValueError("times must be positive")
        self._increment_proposer_priority(times)

    def _increment_proposer_priority(self, times: int) -> None:
        diff_max = PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power()
        self._rescale_priorities(diff_max)
        self._shift_by_avg_proposer_priority()
        prop = None
        for _ in range(times):
            prop = self._single_increment()
        self.proposer = prop

    def _single_increment(self) -> Validator:
        for v in self.validators:
            v.proposer_priority += v.voting_power
        mostest = self.validators[0]
        for v in self.validators[1:]:
            mostest = mostest.compare_proposer_priority(v)
        mostest.proposer_priority -= self.total_voting_power()
        return mostest

    def _rescale_priorities(self, diff_max: int) -> None:
        if diff_max <= 0 or not self.validators:
            return
        prios = [v.proposer_priority for v in self.validators]
        diff = max(prios) - min(prios)
        if diff > diff_max:
            ratio = (diff + diff_max - 1) // diff_max
            for v in self.validators:
                # truncated (toward-zero) division, matching Go int64 /
                q = abs(v.proposer_priority) // ratio
                v.proposer_priority = q if v.proposer_priority >= 0 else -q

    def _shift_by_avg_proposer_priority(self) -> None:
        if not self.validators:
            return
        total = sum(v.proposer_priority for v in self.validators)
        n = len(self.validators)
        avg = total // n if total >= 0 else -((-total) // n)  # trunc toward 0
        for v in self.validators:
            v.proposer_priority -= avg

    def get_proposer(self) -> Validator:
        assert self.validators
        if self.proposer is None:
            self.proposer = self._find_proposer()
        return self.proposer

    def _find_proposer(self) -> Validator:
        mostest = self.validators[0]
        for v in self.validators[1:]:
            mostest = mostest.compare_proposer_priority(v)
        return mostest

    # -- validator updates (reference: validator_set.go:516-646) --

    def update_with_change_set(self, changes: list[Validator]) -> None:
        """Apply ABCI validator updates: power 0 removes, new adds,
        other powers update. New validators start at priority
        -1.125 * new total power (reference: computeNewPriorities)."""
        if not changes:
            return
        seen = set()
        for c in changes:
            if c.address in seen:
                raise ValueError("duplicate address in change set")
            seen.add(c.address)
            if c.voting_power < 0:
                raise ValueError("negative power update")

        removals = {c.address for c in changes if c.voting_power == 0}
        updates = {c.address: c for c in changes if c.voting_power > 0}

        for addr in removals:
            if not self.has_address(addr):
                raise ValueError("removing unknown validator")
        kept = [v for v in self.validators if v.address not in removals]

        new_total = sum(
            updates.get(v.address, v).voting_power for v in kept
        ) + sum(c.voting_power for c in updates.values() if not self.has_address(c.address))
        if new_total == 0:
            raise ValueError("validator set would be empty")
        if new_total > MAX_TOTAL_VOTING_POWER:
            raise ValueError("total voting power would exceed cap")

        out: list[Validator] = []
        for v in kept:
            if v.address in updates:
                nv = updates.pop(v.address).copy()
                nv.proposer_priority = v.proposer_priority
                out.append(nv)
            else:
                out.append(v)
        for c in updates.values():
            nv = c.copy()
            nv.proposer_priority = -(new_total + (new_total >> 3))
            out.append(nv)

        out.sort(key=lambda v: (-v.voting_power, v.address))
        self.validators = out
        self._total = None
        self._shift_by_avg_proposer_priority()

    # -- commit verification (batched; the hot path) --

    def _use_expanded(self, lanes) -> bool:
        """Will _batch_verify_lanes take the expanded device path for
        the ed25519 lanes among `lanes`? The question is asked of the
        set's ed25519 part: enough such lanes in the batch, and the
        ed25519 keys (what the tables hold) within the backend's cap."""
        cols = self._columns()
        if cols.all_ed25519:
            return self._tables_take(cols, len(lanes))
        rows = cols.ed_row[np.asarray(lanes, np.intp)]
        return self._tables_take(cols, int(np.count_nonzero(rows >= 0)))

    def _tables_take(self, cols: _SetColumns, ed_lanes: int) -> bool:
        from ..crypto.tpu import verify as tv

        # Above _MAX_BATCH a single launch is off the table (the
        # BatchVerifier fallback self-splits); e.g. a full fast-sync
        # window at 10k validators.
        return _EXPAND_MIN <= ed_lanes <= tv._MAX_BATCH \
            and self._tables_serve(cols)

    def _tables_resident(self, cols: _SetColumns) -> bool:
        """_tables_take's question asked of the SET, as the live
        consensus path asks it (verify_live): does this set keep comb
        tables on the device at all? Then every batch of its votes
        from the device threshold up is theirs, whatever its length."""
        return len(cols.ed_keys) >= _EXPAND_MIN and self._tables_serve(cols)

    def _tables_serve(self, cols: _SetColumns) -> bool:
        from ..crypto import batch as _batch

        # The valset-size cap is backend-dependent (expanded.max_keys:
        # HBM budget on chips, one build chunk on the CPU backend
        # where tables buy nothing).
        if _batch.host_forced() or not _batch.device_available("ed25519"):
            return False
        try:
            from ..crypto.tpu import expanded

            cap = expanded.max_keys()
        except Exception:
            # max_keys inits the JAX backend; a broken device runtime
            # must degrade to the host path (with the usual breaker
            # cooldown), not crash commit verification.
            _batch.mark_device_failed("ed25519")
            _batch.logger.exception("backend probe failed; host path")
            return False
        return len(cols.ed_keys) <= cap

    def warm_device_tables(self):
        """Kick a background build of this set's expanded device
        tables (crypto/tpu/expanded.py warm_async) — over its ed25519
        keys — if commit verifies for it would use them. Called when a
        validator-set change is adopted so the first commit under the
        new set doesn't pay the table build inline. Returns the thread
        or None."""
        if not self._use_expanded(range(len(self.validators))):
            return None
        from ..crypto.tpu import expanded

        return expanded.warm_async(self._columns().ed_keys)

    def _lane_split(self, cols: _SetColumns, lanes):
        """Positions among `lanes` (validator indices) of the ed25519
        lanes and of the others, each ascending. Only a set that is
        not all ed25519 is ever split."""
        with TRACER.span(tracing.VERIFY_LANE_SPLIT) as span:
            code = cols.kind_code[np.asarray(lanes, np.intp)]
            ed_pos = np.flatnonzero(code == _KIND_ED25519)
            rest_pos = np.flatnonzero(code != _KIND_ED25519)
            sr = int(np.count_nonzero(code == _KIND_SR25519))
            span.set_attr("ed25519", len(ed_pos))
            span.set_attr("sr25519", sr)
            span.set_attr("other", len(rest_pos) - sr)
        return ed_pos, rest_pos

    def structured_or_bytes(self, lanes: list[int], build, materialize):
        """THE structured-vs-full-bytes policy, one copy for every
        call site (commit verify, fast-sync windows, vote scheduler,
        a block's evidence). build(pick) and materialize(pick) give
        the sign bytes of the lanes at positions `pick` (an ascending
        index array; None: every lane): build -> a
        types.sign_batch.StructuredSignBytes, asked for when the
        expanded device path will consume it; ValueError from build
        (hostile timestamps, too many template groups, oversized sign
        bytes) means the input doesn't fit the vectorized layout —
        fall back to materialize()'s full bytes SILENTLY, because
        that's an input property, not a bug. For a set that is not all
        ed25519 the policy is applied to its ed25519 lanes, the others
        get full bytes, and both come back as a SplitSignBytes."""
        cols = self._columns()
        if cols.all_ed25519:
            return self._ed25519_msgs(cols, len(lanes), None, build,
                                      materialize)
        ed_pos, rest_pos = self._lane_split(cols, lanes)
        return SplitSignBytes(
            ed_pos, self._ed25519_msgs(cols, len(ed_pos), ed_pos, build,
                                       materialize),
            rest_pos, materialize(rest_pos) if len(rest_pos) else [])

    def _ed25519_msgs(self, cols, n: int, pick, build, materialize):
        if self._tables_take(cols, n):
            try:
                return build(pick)
            except ValueError:
                pass
        return materialize(pick) if n else []

    def _commit_msgs(self, chain_id: str, commit, slots, lanes,
                     columns: CommitColumns | None = None):
        """Sign bytes for the given commit slots (a list or an index
        array): structured when the device path will consume it,
        materialized otherwise. `columns`: the commit's, where the
        caller has read them."""
        if not len(slots):
            return []
        with TRACER.span(tracing.VERIFY_SIGN_BATCH, lanes=len(slots)):
            return self.structured_or_bytes(
                lanes, *self._commit_builders(chain_id, commit, slots,
                                              columns))

    @staticmethod
    def _commit_builders(chain_id: str, commit, slots, columns):
        """structured_or_bytes' (build, materialize) over commit slots."""
        slots = np.asarray(slots, np.intp)

        def of(pick):
            return slots if pick is None else slots[pick]

        return (lambda pick: CommitSignBatch(chain_id, commit, of(pick),
                                             columns),
                lambda pick: [commit.vote_sign_bytes(chain_id, s)
                              for s in of(pick)])

    def _batch_verify_lanes(self, lanes: list[int], msgs,
                            sigs: list[bytes], rows=None):
        """One device batch over (self.validators[lanes[i]], msgs[i],
        sigs[i]): the ladder of every verify site. The ed25519 lanes
        of a large set go through the expanded per-validator comb
        tables (cached on device across heights — see
        crypto/tpu/expanded.py); everything else through the general
        BatchVerifier, which groups by key type (sr25519 lanes: one
        launch of their kernel). A set that is all ed25519 has nothing
        to partition: its lanes take the ed25519 rungs below as they
        come. A set of several key types is split first (_verify_split),
        and its ed25519 lanes come back here with `rows`, their rows in
        the tables of the set's ed25519 keys.

        msgs is a list of sign-byte blobs, a
        types.sign_batch.StructuredSignBytes (single-commit batch or a
        fast-sync window's merged batch) or, for a set of several key
        types, a SplitSignBytes: the structured form lets the
        expanded path assemble the bytes ON DEVICE (template +
        per-lane timestamp patch) instead of shipping ~190 B of
        redundant sign bytes per lane; every fallback materializes the
        identical full bytes. Verdicts are in the caller's lane
        order."""
        cols = self._columns()
        if rows is None:
            if not cols.all_ed25519:
                return self._verify_split(cols, lanes, msgs, sigs)
            rows = lanes
        # The ed25519 rungs: structured -> full bytes on the tables ->
        # BatchVerifier (general kernel -> host).
        from ..crypto import batch as _batch

        structured = _is_structured(msgs)
        # structured implies the tables were to take the batch when it
        # was built (structured_or_bytes)
        if structured or self._tables_take(cols, len(lanes)):
            from ..libs import failpoints

            try:
                failpoints.hit("device.verify")
                exp = self._expanded(cols)
                if structured:
                    try:
                        verdicts = exp.verify_structured(
                            rows, msgs, sigs)
                    except ValueError:
                        # structural limit (oversized templates /
                        # sign bytes), NOT a device failure: same
                        # device, full-bytes form. Logged loudly —
                        # if this is the lane-0 reassembly self-check
                        # firing, the structured path has a template
                        # bug that must surface, not hide behind a
                        # working fallback.
                        _batch.logger.exception(
                            "structured commit verify rejected the "
                            "batch (%d lanes); using full-bytes form",
                            len(lanes))
                        verdicts = exp.verify(
                            rows, msgs.materialize(), sigs)
                else:
                    verdicts = exp.verify(rows, msgs, sigs)
                return bool(verdicts.all()), verdicts
            except Exception:
                # dead device mid-table-build or mid-launch: degrade
                # to the BatchVerifier (which itself degrades device
                # -> host) instead of failing the commit verify
                _batch.mark_device_failed("ed25519")
                _batch.logger.exception(
                    "expanded-valset verify failed (%d lanes); "
                    "degrading", len(lanes))
        if structured:
            msgs = msgs.materialize()
        bv = BatchVerifier()
        for i, m, s in zip(lanes, msgs, sigs):
            bv.add(self.validators[i].pub_key, m, s)
        return bv.verify()

    def _expanded(self, cols: _SetColumns):
        """The comb tables of this set's ed25519 keys, from the
        process's cache or built now."""
        from ..crypto.tpu import expanded

        with TRACER.span(tracing.VERIFY_TABLES,
                         keys=len(cols.ed_keys)) as tspan:
            held = cols.digest is not None
            tspan.set_attr("digest", "held" if held else "hashed")
            if not held:
                cols.digest = expanded.key_digest(cols.ed_keys)
            return expanded.get_expanded(cols.ed_keys, cols.digest)

    # -- the live consensus path: one lane count a launch --

    def verify_live(self, lanes: list[int], build, materialize,
                    sigs: list[bytes], launch_lanes: int) -> np.ndarray:
        """Per-lane verdicts for votes verified as consensus runs (the
        vote scheduler's micro-batches, the lanes of a LastCommit the
        speculation plane holds no verdict for): structured_or_bytes
        and _batch_verify_lanes in one, with `build` and `materialize`
        as the former takes them.

        For a set whose comb tables are resident (_tables_resident:
        the question is the SET's here, not the batch's) every ed25519
        launch is the structured program at exactly `launch_lanes`
        lanes, the consensus config's vote_batch_max: a shorter batch
        is padded, a longer one goes as several launches, and a tail
        shorter than the device threshold stays on the host, as every
        batch that short does. So a node meets ONE program here
        whatever the timing cuts (load_live_programs loads it when
        consensus starts), where _bucket's ladder is fourteen for a
        10,000-key set and a cold one holds the executor for ~50 s in
        the middle of a round. Lanes that do not fit the structured
        layout (build's ValueError: hostile timestamps, too many
        template groups, oversized sign bytes) are the host's. The
        other key types of a mixed set go as they always do: one
        BatchVerifier. Any other set takes the ordinary ladder."""
        cols = self._columns()
        if not self._tables_resident(cols):
            msgs = self.structured_or_bytes(lanes, build, materialize)
            return np.asarray(
                self._batch_verify_lanes(lanes, msgs, sigs)[1], bool)
        from ..crypto.batch import _DEVICE_THRESHOLD

        verdicts = np.zeros(len(lanes), bool)
        if cols.all_ed25519:
            ed_pos = np.arange(len(lanes))
        else:
            ed_pos, rest_pos = self._lane_split(cols, lanes)
            if len(rest_pos):
                verdicts[rest_pos] = self._verify_host_or_general(
                    lanes, rest_pos, materialize, sigs)
        for lo in range(0, len(ed_pos), launch_lanes):
            pick = ed_pos[lo:lo + launch_lanes]
            on_tables = None
            if len(pick) >= _DEVICE_THRESHOLD:
                on_tables = self._verify_on_tables(
                    cols, lanes, pick, build, sigs, launch_lanes)
            verdicts[pick] = on_tables if on_tables is not None else \
                self._verify_host_or_general(lanes, pick, materialize,
                                             sigs, use_device=False)
        return verdicts

    def _verify_on_tables(self, cols, lanes, pick, build, sigs,
                          launch_lanes: int):
        """One structured launch of `launch_lanes` lanes over the lanes
        at positions `pick`; None where the host has to take them (the
        input does not fit the layout, or the device failed: then its
        breaker is open as after any failed launch)."""
        from ..crypto import batch as _batch
        from ..libs import failpoints

        try:
            with TRACER.span(tracing.VERIFY_SIGN_BATCH, lanes=len(pick)):
                sbatch = build(pick)
        except ValueError:
            return None
        rows = [lanes[i] for i in pick]
        if not cols.all_ed25519:
            rows = cols.ed_row[rows]
        try:
            failpoints.hit("device.verify")
            return self._expanded(cols).verify_structured(
                rows, sbatch, [sigs[i] for i in pick], lanes=launch_lanes)
        except ValueError:
            _batch.logger.exception(
                "structured vote verify rejected the batch (%d lanes); "
                "host path", len(pick))
        except Exception:
            _batch.mark_device_failed("ed25519")
            _batch.logger.exception(
                "expanded-valset verify failed (%d lanes); degrading",
                len(pick))
        return None

    def _verify_host_or_general(self, lanes, pick, materialize, sigs,
                                use_device=None) -> np.ndarray:
        """The lanes at positions `pick` through a BatchVerifier: on
        the host (`use_device` False: verify_live's ed25519 lanes that
        the tables did not take) or wherever it sends their key type."""
        bv = BatchVerifier(use_device=use_device)
        for i, msg in zip(pick, materialize(pick)):
            bv.add(self.validators[lanes[i]].pub_key, msg, sigs[i])
        return bv.verify()[1]

    def tables_resident(self) -> bool:
        """Does the live path verify this set's votes on resident
        comb tables (verify_live)?"""
        return self._tables_resident(self._columns())

    def load_live_programs(self, launch_lanes: int) -> int:
        """Load what verify_live launches for this set, its tables
        built first if they are not: called when consensus starts, so
        that no vote waits for a compile. Returns the programs loaded
        (0: the set has no resident tables and verify_live launches
        nothing of its own)."""
        cols = self._columns()
        if not self._tables_resident(cols):
            return 0
        return self._expanded(cols).load_structured(launch_lanes)

    def verify_commit_lanes_live(self, chain_id: str, commit, slots,
                                 launch_lanes: int,
                                 columns: CommitColumns | None = None
                                 ) -> np.ndarray:
        """verify_live over the given slots of a commit (the lanes of
        a LastCommit that still need a verdict). `columns`: the
        commit's, where the caller has read them."""
        if columns is None:
            columns = CommitColumns(commit)
        return self.verify_live(
            [int(s) for s in slots],
            *self._commit_builders(chain_id, commit, slots, columns),
            [commit.signatures[s].signature for s in slots], launch_lanes)

    def _verify_split(self, cols: _SetColumns, lanes, msgs, sigs):
        """_batch_verify_lanes for a set of several key types: the
        ed25519 lanes back through the ladder at their rows of the
        tables, the others in ONE BatchVerifier (sr25519: one launch),
        verdicts in the caller's lane order."""
        if isinstance(msgs, SplitSignBytes):
            split = msgs
        else:
            ed_pos, rest_pos = self._lane_split(cols, lanes)
            split = SplitSignBytes(ed_pos, [msgs[i] for i in ed_pos],
                                   rest_pos, [msgs[i] for i in rest_pos])
        verdicts = np.zeros(len(lanes), bool)
        ed_pos, rest_pos = split.ed_pos.tolist(), split.rest_pos.tolist()
        if ed_pos:
            ed_lanes = [lanes[i] for i in ed_pos]
            _, verdicts[split.ed_pos] = self._batch_verify_lanes(
                ed_lanes, split.ed_msgs, [sigs[i] for i in ed_pos],
                rows=cols.ed_row[ed_lanes])
        if rest_pos:
            bv = BatchVerifier()
            for i, m in zip(rest_pos, split.rest_msgs):
                bv.add(self.validators[lanes[i]].pub_key, m, sigs[i])
            _, verdicts[split.rest_pos] = bv.verify()
        return bool(verdicts.all()), verdicts
    def light_selection(self, cols: CommitColumns, need: int):
        """VerifyCommitLight's selection over a commit's columns: the
        for-block slots (an index array) up to and including the first
        that carries the tally past 2/3 (3 * tally > need), their
        signatures and that tally; every for-block slot when none
        does. Shared with the fast-sync window builder
        (blockchain/verify_ahead.py)."""
        slots = np.flatnonzero(cols.for_block)
        if not slots.size:
            return slots, [], 0
        tally = np.cumsum(self._columns().power[slots])
        over = np.flatnonzero(3 * tally > need)
        if over.size:
            slots = slots[:over[0] + 1]
        return (slots, cols.signatures(cols.for_block[:slots[-1] + 1]),
                int(tally[len(slots) - 1]))

    def verify_commit(self, chain_id: str, block_id: BlockID, height: int,
                      commit, launch_lanes: int | None = None) -> None:
        """Verify ALL non-absent signatures; tally for-block power must
        exceed 2/3 (reference: validator_set.go:662). `launch_lanes`:
        the caller is the live consensus path, whose launches have
        that many lanes each (verify_live)."""
        with TRACER.span(tracing.VERIFY_COMMIT, form="full") as span:
            self._verify_commit(chain_id, block_id, height, commit, span,
                                launch_lanes)

    def _verify_commit(self, chain_id: str, block_id: BlockID,
                       height: int, commit, span,
                       launch_lanes: int | None = None) -> None:
        with TRACER.span(tracing.VERIFY_COLLECT):
            self._check_commit_basics(block_id, height, commit)
            cols = CommitColumns(commit)
            mine = self._columns()
            bad = cols.wrong_address(mine.addresses)
            if bad is not None:
                raise VerificationError(
                    f"wrong validator address in slot {bad}")
            slots = np.flatnonzero(cols.present)
            lanes = slots.tolist()
            sigs = cols.signatures(cols.present)
            tallied = int(mine.power[cols.for_block].sum())
        span.set_attr("lanes", len(lanes))
        if launch_lanes:
            verdicts = self.verify_commit_lanes_live(
                chain_id, commit, slots, launch_lanes, cols)
            ok = bool(verdicts.all())
        else:
            msgs = self._commit_msgs(chain_id, commit, slots, lanes, cols)
            span.set_attr("structured", _is_structured(msgs))
            ok, verdicts = self._batch_verify_lanes(lanes, msgs, sigs)
        if not ok:
            bad = [lanes[i] for i in range(len(lanes)) if not verdicts[i]]
            raise VerificationError(f"invalid signature(s) at index(es) {bad}")
        if 3 * tallied <= 2 * self.total_voting_power():
            raise VerificationError(
                f"insufficient voting power: {tallied} of {self.total_voting_power()}"
            )

    def plan_commit_light(self, chain_id: str, block_id: BlockID,
                          height: int, commit) -> CommitVerifyPlan:
        """Selection half of verify_commit_light: basics + the
        cheapest 2/3 of for-block power, NO signature work. Raises
        VerificationError before planning any cryptography when the
        power cannot reach the threshold."""
        need = 2 * self.total_voting_power()
        with TRACER.span(tracing.VERIFY_COLLECT):
            self._check_commit_basics(block_id, height, commit)
            cols = CommitColumns(commit)
            slots, sigs, tallied = self.light_selection(cols, need)
        if 3 * tallied <= need:
            raise VerificationError(
                f"insufficient voting power: {tallied} of {self.total_voting_power()}"
            )
        lanes = slots.tolist()
        msgs = self._commit_msgs(chain_id, commit, slots, lanes, cols)
        return CommitVerifyPlan(self, lanes, lanes, sigs, msgs, "light")

    def verify_commit_light(self, chain_id: str, block_id: BlockID,
                            height: int, commit) -> None:
        """Verify only the for-block signatures needed to pass 2/3
        (reference: validator_set.go:720) — as one batch."""
        self.plan_commit_light(chain_id, block_id, height,
                               commit).execute()

    def plan_commit_trusting(self, chain_id: str, commit,
                             trust_num: int,
                             trust_den: int) -> CommitVerifyPlan:
        """Selection half of verify_commit_light_trusting: address
        matching + the trust-level power tally, NO signature work.
        Raises VerificationError (insufficient trusted power / double
        vote) before planning any cryptography."""
        if trust_den <= 0 or trust_num <= 0 or trust_num > trust_den:
            raise ValueError("invalid trust level")
        lanes: list[int] = []  # OUR validator indices (for the tables)
        slots: list[int] = []  # commit slots (for sign bytes/errors)
        sigs: list[bytes] = []
        tallied = 0
        need = self.total_voting_power() * trust_num
        seen: set[int] = set()
        with TRACER.span(tracing.VERIFY_COLLECT):
            for idx, cs in enumerate(commit.signatures):
                if not cs.for_block():
                    continue
                vi, val = self.get_by_address(cs.validator_address)
                if vi < 0:
                    continue
                if vi in seen:
                    raise VerificationError(
                        "double vote from same validator")
                seen.add(vi)
                lanes.append(vi)
                slots.append(idx)
                sigs.append(cs.signature)
                tallied += val.voting_power
                if tallied * trust_den > need:
                    break
        if tallied * trust_den <= need:
            raise VerificationError(
                f"insufficient trusted power: {tallied}"
            )
        msgs = self._commit_msgs(chain_id, commit, slots, lanes)
        return CommitVerifyPlan(self, lanes, slots, sigs, msgs,
                                "trusting")

    def verify_commit_light_trusting(self, chain_id: str, commit,
                                     trust_num: int, trust_den: int) -> None:
        """Trust-fraction variant for light-client skipping verification
        (reference: validator_set.go:776). Validators are matched by
        ADDRESS (the commit came from a possibly newer set)."""
        self.plan_commit_trusting(chain_id, commit, trust_num,
                                  trust_den).execute()

    def _check_commit_basics(self, block_id: BlockID, height: int, commit) -> None:
        if commit is None:
            raise VerificationError("nil commit")
        if len(self.validators) != len(commit.signatures):
            raise VerificationError(
                f"commit has {len(commit.signatures)} sigs, valset has "
                f"{len(self.validators)}"
            )
        if height != commit.height:
            raise VerificationError(f"commit height {commit.height} != {height}")
        if commit.block_id != block_id:
            raise VerificationError("commit is for a different block")

    def __repr__(self) -> str:
        return f"ValidatorSet(n={len(self.validators)}, power={self.total_voting_power()})"
