"""State store (reference: state/store.go:74-81).

Persists: the current State, one validator-set row a height, consensus
params, and per-height ABCI responses for replay.

Validator sets are sparse, as the reference's are (state/store.go
saveValidatorsInfo / lastStoredHeightFor). A set's MEMBERSHIP (keys,
powers, order) is written in full only in the row of the height at
which it takes effect: the first heights saved, every
`last_height_validators_changed`, every `VALSET_CHECKPOINT`th height,
every row `bootstrap` writes. Every other height row, and each of the
three sets of the state row, is a SET RECORD that names the height of
the full row its membership is in and carries what does move every
block: the proposer priorities as a packed column, and the proposer.
Where the reference replays `IncrementProposerPriority` over the
distance from that row, this store reads the column: a set read back is
the set that was written, at any distance.

Rows (`_FORMAT` is their first byte; a row written before this format
is JSON, starts with "{", and still loads):

    set record  = >BQ32sBII (format, base, membership digest,
                  proposer-address bytes, n, membership bytes) |
                  proposer address | n x >q priorities | membership
                  base: height of the full row the membership is in;
                  0 = it follows
                  digest: ValidatorSet.membership_digest(), by which a
                  save tells whether the row beneath holds the live
                  set's membership, and a load that the full row still
                  holds what the record was written over
    membership  = per validator, in order: >20sqBB (address, power,
                  key-type bytes, key bytes) | key type | key
    height row  validatorsKey:<h> = one set record
    state row   stateKey = format | >I length | JSON of the scalars |
                  the records of validators, next_validators,
                  last_validators"""

from __future__ import annotations

import json
import struct
from operator import attrgetter

from ..libs import tracing
from ..libs.db import DB
from ..libs.tracing import TRACER
from ..types.block import BlockID, PartSetHeader
from ..types.params import ConsensusParams
from ..types.validator import Validator
from ..types.validator_set import ValidatorSet
from ..abci import types as abci_types
from . import State

VALSET_CHECKPOINT = 100000  # reference: valSetCheckpointInterval

_STATE_KEY = b"stateKey"
_FORMAT = 1
_LEGACY = b"{"  # first byte of a row of the JSON era
_RECORD = struct.Struct(">BQ32sBII")
_MEMBER = struct.Struct(">20sqBB")
_SETS = ("validators", "next_validators", "last_validators")
_priority = attrgetter("proposer_priority")


def _h(height: int) -> bytes:
    return struct.pack(">Q", height)


def _valset_key(height: int) -> bytes:
    return b"validatorsKey:" + _h(height)


# The JSON form of a whole set: light/store.py's rows, and the state
# store's own before set records.

def _valset_to_json(vs: ValidatorSet) -> dict:
    return {
        "validators": [
            {
                "pub_key_type": v.pub_key.type_name,
                "pub_key": v.pub_key.bytes().hex(),
                "power": v.voting_power,
                "priority": v.proposer_priority,
            }
            for v in vs.validators
        ],
        "proposer": vs.proposer.address.hex() if vs.proposer else None,
    }


def _valset_from_json(d: dict) -> ValidatorSet:
    from .. import crypto

    vs = ValidatorSet([])
    for vd in d["validators"]:
        pk = crypto.pubkey_from_type_and_bytes(
            vd["pub_key_type"], bytes.fromhex(vd["pub_key"])
        )
        val = Validator.new(pk, vd["power"])
        val.proposer_priority = vd["priority"]
        vs.validators.append(val)
    if d.get("proposer"):
        i, v = vs.get_by_address(bytes.fromhex(d["proposer"]))
        vs.proposer = v
    return vs


# -- set records: the one encoder and decoder of the height rows and
# the state row --

def _membership_bytes(vs: ValidatorSet) -> bytes:
    out = []
    for v in vs.validators:
        kind = v.pub_key.type_name.encode()
        key = v.pub_key.bytes()
        out += (_MEMBER.pack(v.address, v.voting_power, len(kind),
                             len(key)), kind, key)
    return b"".join(out)


def _membership_from_bytes(data: bytes) -> list[Validator]:
    from .. import crypto

    out = []
    pos = 0
    while pos < len(data):
        address, power, kind_len, key_len = _MEMBER.unpack_from(data, pos)
        key_at = pos + _MEMBER.size + kind_len
        pos = key_at + key_len
        if pos > len(data):
            raise ValueError("truncated validator set membership")
        out.append(Validator(address, crypto.pubkey_from_type_and_bytes(
            data[key_at - kind_len:key_at].decode(), data[key_at:pos]),
            power))
    return out


def _encode_set(vs: ValidatorSet, base: int, membership: bytes = b"") -> bytes:
    """`base`: the height of the full row that holds this set's
    membership, or 0 with the `membership` bytes to follow."""
    vals = vs.validators
    proposer = vs.proposer.address if vs.proposer is not None else b""
    return b"".join((
        _RECORD.pack(_FORMAT, base, vs.membership_digest(), len(proposer),
                     len(vals), len(membership)),
        proposer,
        struct.pack(">%dq" % len(vals), *map(_priority, vals)),
        membership,
    ))


def _read_record(raw: bytes, pos: int = 0):
    """The set record at raw[pos]: (base, membership digest, proposer
    address, priorities, membership bytes, where the record ends)."""
    fmt, base, digest, plen, n, mlen = _RECORD.unpack_from(raw, pos)
    if fmt != _FORMAT:
        raise ValueError(f"unknown validator set record format {fmt}")
    at = pos + _RECORD.size
    end = at + plen + 8 * n + mlen
    if end > len(raw):
        raise ValueError("truncated validator set record")
    return (base, digest, raw[at:at + plen],
            struct.unpack_from(">%dq" % n, raw, at + plen),
            raw[end - mlen:end], end)


def _decode_set(raw: bytes, pos: int, members_at) -> tuple[ValidatorSet, int]:
    """The set whose record starts at raw[pos], and where the record
    ends. `members_at(height)` hands out the validators of a full row,
    as objects this set may keep, and that row's membership digest."""
    base, digest, proposer, priorities, membership, end = \
        _read_record(raw, pos)
    vals, held = members_at(base) if base \
        else (_membership_from_bytes(membership), digest)
    if len(vals) != len(priorities) or held != digest:
        raise ValueError(
            f"validator set row {base} does not hold the membership "
            f"a record of {len(priorities)} validators was written over")
    for v, priority in zip(vals, priorities):
        v.proposer_priority = priority
    vs = ValidatorSet([])
    vs.validators = vals
    if proposer:
        vs.proposer = vs.get_by_address(proposer)[1]
    return vs, end


def _if_holds(found: tuple[int, bytes] | None, vs: ValidatorSet) -> int | None:
    """The full row's height out of `Store._full_row_under`, if that
    row holds `vs`'s membership; None where whoever asks has to write
    the membership out."""
    if found is not None and found[1] == vs.membership_digest():
        return found[0]
    return None


class Store:
    def __init__(self, db: DB):
        self.db = db

    # -- state --

    def save(self, state: State) -> None:
        self.db.write_batch(self._save_ops(state))

    def _save_ops(self, state: State) -> list[tuple[bytes, bytes | None]]:
        """ONE batch: the row of next_validators' height and the state
        row. The height row is full where the set changes there
        (`last_height_validators_changed`, or a membership other than
        the row's beneath it), at a checkpoint, and where there is no
        row beneath it; else it rests where that row does."""
        next_height = state.last_block_height + 1
        ops: list[tuple[bytes, bytes | None]] = []
        if next_height == 1:
            # the first save, and the one after InitChain amends the
            # genesis set: the rows of both heights, in full
            next_height = state.initial_height
            ops.append(self._full_row(next_height, state.validators))
            on_vals, on_next, on_last = next_height, None, None
        else:
            beneath = self._full_row_under(next_height)
            on_vals = _if_holds(beneath, state.validators)
            on_next = _if_holds(beneath, state.next_validators)
            on_last = _if_holds(self._full_row_under(next_height - 1),
                                state.last_validators)
        if (on_next is None
                or state.last_height_validators_changed == next_height + 1
                or (next_height + 1) % VALSET_CHECKPOINT == 0):
            ops.append(self._full_row(next_height + 1,
                                      state.next_validators))
            on_next = next_height + 1
        else:
            ops.append((_valset_key(next_height + 1),
                        _encode_set(state.next_validators, on_next)))
        ops += self._params_ops(next_height, state.consensus_params,
                                state.last_height_consensus_params_changed)
        ops.append((_STATE_KEY,
                    self._state_bytes(state, (on_vals, on_next, on_last))))
        return ops

    def _state_bytes(self, state: State, rests_on) -> bytes:
        """`rests_on`: for each of `_SETS`, the height of the full row
        that holds its membership; None where no row does, and the
        membership goes into the state row itself."""
        bid = state.last_block_id
        scalars = json.dumps({
            "chain_id": state.chain_id,
            "initial_height": state.initial_height,
            "last_block_height": state.last_block_height,
            "last_block_id": {
                "hash": bid.hash.hex(),
                "psh_total": bid.part_set_header.total if bid.part_set_header else 0,
                "psh_hash": bid.part_set_header.hash.hex() if bid.part_set_header else "",
            },
            "last_block_time": state.last_block_time,
            "last_height_validators_changed": state.last_height_validators_changed,
            "consensus_params": state.consensus_params.to_json(),
            "last_height_consensus_params_changed":
                state.last_height_consensus_params_changed,
            "last_results_hash": state.last_results_hash.hex(),
            "app_hash": state.app_hash.hex(),
            "app_version": state.app_version,
        }).encode()
        height = state.last_block_height + 1
        records = []
        for name, base, at in zip(_SETS, rests_on,
                                  (height, height + 1, height - 1)):
            vs = getattr(state, name)
            records.append(
                _encode_set(vs, base) if base is not None
                else _encode_set(vs, 0, self._membership(at, vs)))
        return b"".join((bytes([_FORMAT]),
                         struct.pack(">I", len(scalars)), scalars,
                         *records))

    def load(self) -> State | None:
        raw = self.db.get(_STATE_KEY)
        if raw is None:
            return None
        if raw[:1] == _LEGACY:
            d = json.loads(raw)
            sets = {name: _valset_from_json(d[name]) for name in _SETS}
        else:
            (size,) = struct.unpack_from(">I", raw, 1)
            pos = 5 + size
            d = json.loads(raw[5:pos])
            parsed: dict[int, tuple[list[Validator], bytes]] = {}

            def members_at(height: int):
                # two of the sets may rest on one row: parse it once
                if height not in parsed:
                    parsed[height] = self._members_at(height)
                vals, digest = parsed[height]
                return [v.copy() for v in vals], digest

            sets = {}
            for name in _SETS:
                sets[name], pos = _decode_set(raw, pos, members_at)
        bd = d["last_block_id"]
        psh = (
            PartSetHeader(bd["psh_total"], bytes.fromhex(bd["psh_hash"]))
            if bd["psh_total"] else None
        )
        return State(
            chain_id=d["chain_id"],
            initial_height=d["initial_height"],
            last_block_height=d["last_block_height"],
            last_block_id=BlockID(bytes.fromhex(bd["hash"]), psh),
            last_block_time=d["last_block_time"],
            last_height_validators_changed=d["last_height_validators_changed"],
            consensus_params=ConsensusParams.from_json(d["consensus_params"]),
            last_height_consensus_params_changed=
                d["last_height_consensus_params_changed"],
            last_results_hash=bytes.fromhex(d["last_results_hash"]),
            app_hash=bytes.fromhex(d["app_hash"]),
            app_version=d.get("app_version", 0),
            **sets,
        )

    def bootstrap(self, state: State) -> None:
        """Seed the store from an out-of-band trusted state (statesync;
        reference state/store.go:188). ONE batch: these rows used to go
        out as four separate write_batch calls plus a set, so a crash
        mid-bootstrap could leave a height with a validator set but no
        state row (or vice versa) — a skew no startup reconciler can
        tell apart from corruption. All-or-nothing now. Every height
        row it writes is full: there is nothing beneath them."""
        height = state.last_block_height + 1
        if height == 1:
            height = state.initial_height
        ops: list[tuple[bytes, bytes | None]] = []
        on_last = None
        if height > 1 and len(state.last_validators):
            ops.append(self._full_row(height - 1, state.last_validators))
            on_last = height - 1
        ops.append(self._full_row(height, state.validators))
        ops.append(self._full_row(height + 1, state.next_validators))
        ops += self._params_ops(height, state.consensus_params,
                                state.last_height_consensus_params_changed)
        ops.append((_STATE_KEY,
                    self._state_bytes(state, (height, height + 1, on_last))))
        self.db.write_batch(ops)

    # -- validator sets (sparse: see the module docstring) --

    def _membership(self, height: int, vs: ValidatorSet) -> bytes:
        """A set's membership in full, under the span that counts how
        often it is still encoded."""
        if not vs.validators:
            return b""
        with TRACER.span(tracing.STATE_VALSET_ROW, height=height,
                         keys=len(vs.validators)) as span:
            data = _membership_bytes(vs)
            span.set_attr("bytes", len(data))
        return data

    def _full_row(self, height: int, vs: ValidatorSet):
        return (_valset_key(height),
                _encode_set(vs, 0, self._membership(height, vs)))

    def _full_row_under(self, height: int) -> tuple[int, bytes] | None:
        """Where the membership of `height`'s row is, and its digest:
        (the height of the full row it rests on, its own where the row
        is full; the membership digest); None where there is no row."""
        raw = self.db.get(_valset_key(height))
        if raw is None:
            return None
        if raw[:1] == _LEGACY:
            return height, \
                _valset_from_json(json.loads(raw)).membership_digest()
        base, digest = _RECORD.unpack_from(raw)[1:3]
        return base or height, digest

    def _members_at(self, height: int) -> tuple[list[Validator], bytes]:
        """The validators of the full row at `height`, parsed anew,
        and the row's membership digest."""
        raw = self.db.get(_valset_key(height))
        if raw is None:
            raise ValueError(f"no validator set row at height {height} "
                             "for a record that rests on it")
        if raw[:1] == _LEGACY:
            vs = _valset_from_json(json.loads(raw))
            return vs.validators, vs.membership_digest()
        base, digest, _, _, membership, _ = _read_record(raw)
        if base:
            raise ValueError(
                f"validator set row {height} holds no membership")
        return _membership_from_bytes(membership), digest

    def save_validator_set(self, height: int, vs: ValidatorSet) -> None:
        self.db.write_batch([self._full_row(height, vs)])

    def load_validators(self, height: int) -> ValidatorSet | None:
        raw = self.db.get(_valset_key(height))
        if raw is None:
            return None
        if raw[:1] == _LEGACY:
            return _valset_from_json(json.loads(raw))
        return _decode_set(raw, 0, self._members_at)[0]

    # -- consensus params (sparse via last-changed pointer) --

    def _params_ops(self, height: int, params: ConsensusParams,
                    last_changed: int):
        return [(b"consensusParamsKey:" + _h(height),
                 json.dumps({
                     "params": params.to_json(),
                     "last_changed": last_changed,
                 }).encode())]

    def load_consensus_params(self, height: int) -> ConsensusParams | None:
        raw = self.db.get(b"consensusParamsKey:" + _h(height))
        if raw is None:
            return None
        return ConsensusParams.from_json(json.loads(raw)["params"])

    # -- ABCI responses (for replay + RPC block_results) --

    def save_abci_responses(self, height: int, responses: dict) -> None:
        """responses: {"deliver_txs": [ResponseDeliverTx], "begin_block":
        ResponseBeginBlock, "end_block": ResponseEndBlock}."""
        self.db.set(
            b"abciResponsesKey:" + _h(height),
            json.dumps({
                "deliver_txs": [
                    abci_types.encode_msg(r).decode()
                    for r in responses.get("deliver_txs", [])
                ],
                "begin_block": abci_types.encode_msg(
                    responses["begin_block"]
                ).decode() if responses.get("begin_block") else None,
                "end_block": abci_types.encode_msg(
                    responses["end_block"]
                ).decode() if responses.get("end_block") else None,
            }).encode(),
        )

    def load_abci_responses(self, height: int) -> dict | None:
        raw = self.db.get(b"abciResponsesKey:" + _h(height))
        if raw is None:
            return None
        d = json.loads(raw)
        return {
            "deliver_txs": [
                abci_types.decode_msg(s.encode()) for s in d["deliver_txs"]
            ],
            "begin_block": abci_types.decode_msg(d["begin_block"].encode())
                if d["begin_block"] else None,
            "end_block": abci_types.decode_msg(d["end_block"].encode())
                if d["end_block"] else None,
        }

    # -- pruning (reference state/store.go:223) --

    def prune_states(self, from_height: int, to_height: int) -> None:
        """Delete the rows of [from_height, to_height). The heights
        kept rest on full rows at or above to_height, or on the one
        that to_height itself rests on, which therefore stays
        (reference state/store.go PruneStates keepVals)."""
        if from_height <= 0 or to_height <= from_height:
            return
        keep, _ = self._full_row_under(to_height) or (None, None)
        ops: list[tuple[bytes, bytes | None]] = []
        for height in range(from_height, to_height):
            if height != keep and height % VALSET_CHECKPOINT != 0:
                ops.append((_valset_key(height), None))
            ops.append((b"consensusParamsKey:" + _h(height), None))
            ops.append((b"abciResponsesKey:" + _h(height), None))
        self.db.write_batch(ops)
