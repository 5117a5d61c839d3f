"""Plain reference: the light client's verification rules, written from
the reference's `light/verifier.go` (`VerifyAdjacent`,
`VerifyNonAdjacent`, `verifyNewHeaderAndVals`, `HeaderExpired`),
`light/client.go` (`verifyLightBlock`'s three cases, `verifySkipping`'s
bisection, `backwards`), `types/validator_set.go` (`VerifyCommitLight`,
`VerifyCommitLightTrusting`, each stopping at the first signature that
carries its tally over the threshold, so the LANES a check verifies are
defined) and `types/block.go` (`Header.Hash`). It imports nothing of
the program: the copies beside it give the canonical sign bytes, the
ZIP-215 verifier and the Merkle tree.

A light block is a plain dict:

    {"header": {version_block, version_app, chain_id, height, time,
                last_block_id: None | (hash, parts_total, parts_hash),
                last_commit_hash, data_hash, validators_hash,
                next_validators_hash, consensus_hash, app_hash,
                last_results_hash, evidence_hash, proposer_address},
     "validators": [(public key, power), ...]   in set order,
     "commit": {"height", "round", "block_hash", "parts_total",
                "parts_hash",
                "sigs": [(flag, address, time_ns, signature), ...]}}

`LightModel` holds a trusted store and is replayed beside a client or a
serving plane: `begin(height)` says from which trusted block a height
is verified and by which of the three cases, `step(trusted, block)`
gives one verification's lanes and verdict, `verify(height)` is the
whole of `verifyLightBlock` for one caller at a time. A refusal is
`Refused(kind)`, kind one of INVALID (the reference's ErrInvalidHeader
and every failure of ValidateBasic), CANT_BE_TRUSTED
(ErrNewValSetCantBeTrusted: too little of the trusted power signed; it
drives bisection), EXPIRED (ErrOldHeaderExpired), BACKWARDS (a broken
hash link).

One departure from the reference's text, noted: its
VerifyCommitLightTrusting verifies each signature as it tallies, so a
commit that carries too little trusted power AND a bad signature among
the matched ones fails on the signature; here (as in any batched form)
the tally comes first and such a commit is CANT_BE_TRUSTED. A commit
with enough trusted power and a bad signature among the lanes is
INVALID in both.

`verify_sig` defaults to the copied ZIP-215 verifier. A caller with
hundreds of thousands of lanes hands in one that runs the copy on a
seeded sample and on every planted lane and takes the generator's own
record for the rest, and says so (the benchmark's `check()`).
"""

from __future__ import annotations

import bisect

from benchmark.reference import canonical
from benchmark.reference import ed25519_zip215 as ref
from benchmark.reference.valset_model import merkle_root, validators_hash

ABSENT, COMMIT, NIL = 1, 2, 3          # BlockIDFlag
MAX_CLOCK_DRIFT_NS = 10 * 1_000_000_000  # defaultMaxClockDrift

INVALID = "invalid"
CANT_BE_TRUSTED = "cant_be_trusted"
EXPIRED = "expired"
BACKWARDS = "backwards"

FORWARD, BETWEEN, BACKWARD = "forward", "between", "backward"


class Refused(Exception):
    def __init__(self, kind: str, why: str):
        super().__init__(f"{kind}: {why}")
        self.kind = kind


# ------------------------------------------------------------ the header


def _bytes_field(field: int, b: bytes) -> bytes:
    return b"" if not b else (
        canonical.varint((field << 3) | 2) + canonical.varint(len(b)) + b)


def _varint_field(field: int, v: int) -> bytes:
    return b"" if not v else canonical.varint(field << 3) + canonical.varint(v)


def _block_id_bytes(bid) -> bytes:
    """tmproto.BlockID; part_set_header is not nullable, so it is
    written even when empty (a genesis header's zero last_block_id)."""
    if bid is None:
        return b"\x12\x00"
    block_hash, total, parts_hash = bid
    psh = _varint_field(1, total) + _bytes_field(2, parts_hash)
    return (_bytes_field(1, block_hash)
            + b"\x12" + canonical.varint(len(psh)) + psh)


def header_hash(h: dict) -> bytes:
    """Header.Hash: the Merkle root over the fourteen fields, each in
    its proto encoding (a byte field wrapped as BytesValue)."""
    return merkle_root([
        _varint_field(1, h["version_block"])
        + _varint_field(2, h["version_app"]),
        _bytes_field(1, h["chain_id"].encode()),
        _varint_field(1, h["height"]),
        _varint_field(1, h["time"] // 1_000_000_000)
        + _varint_field(2, h["time"] % 1_000_000_000),
        _block_id_bytes(h["last_block_id"]),
        _bytes_field(1, h["last_commit_hash"]),
        _bytes_field(1, h["data_hash"]),
        _bytes_field(1, h["validators_hash"]),
        _bytes_field(1, h["next_validators_hash"]),
        _bytes_field(1, h["consensus_hash"]),
        _bytes_field(1, h["app_hash"]),
        _bytes_field(1, h["last_results_hash"]),
        _bytes_field(1, h["evidence_hash"]),
        _bytes_field(1, h["proposer_address"]),
    ])


def block_hash(block: dict) -> bytes:
    """The header's hash, computed once a dict."""
    got = block.get("_hash")
    if got is None:
        got = block["_hash"] = header_hash(block["header"])
    return got


def sign_bytes(chain_id: str, commit: dict, time_ns: int) -> bytes:
    pre, suf = canonical.vote_sign_parts(
        chain_id, commit["height"], commit["round"], commit["block_hash"],
        commit["parts_total"], commit["parts_hash"])
    return canonical.with_timestamp(pre, suf, time_ns)


# ---------------------------------------------------------- the verifier


class LightModel:
    def __init__(self, chain_id: str, period_ns: int,
                 trust_level: tuple[int, int] = (1, 3),
                 verify_sig=ref.verify,
                 max_clock_drift_ns: int = MAX_CLOCK_DRIFT_NS):
        self.chain_id = chain_id
        self.period_ns = period_ns
        self.trust_level = trust_level
        self.verify_sig = verify_sig
        self.max_clock_drift_ns = max_clock_drift_ns
        self.store: dict[int, dict] = {}     # trusted blocks by height
        self._heights: list[int] = []

    # -- the trusted store --

    def trust(self, block: dict) -> None:
        h = block["header"]["height"]
        if h not in self.store:
            bisect.insort(self._heights, h)
        self.store[h] = block

    def heights(self) -> list[int]:
        return list(self._heights)

    def initialize(self, block: dict, trust_hash: bytes) -> None:
        """The trust root: its hash is the one given out of band and
        +2/3 of its own set signed it."""
        self.validate_basic(block)
        if block_hash(block) != trust_hash:
            raise Refused(INVALID, "trust root's hash")
        self.verify_commit_light(block)
        self.trust(block)

    # -- the checks of one block --

    def validate_basic(self, block: dict) -> None:
        """SignedHeader.ValidateBasic + LightBlock.ValidateBasic, as
        far as a forged block can fail them."""
        h, c = block["header"], block["commit"]
        if h["chain_id"] != self.chain_id:
            raise Refused(INVALID, "another chain")
        if c["height"] != h["height"]:
            raise Refused(INVALID, "commit height != header height")
        if c["block_hash"] != block_hash(block):
            raise Refused(INVALID, "commit is for a different block")
        if validators_hash(block["validators"]) != h["validators_hash"]:
            raise Refused(INVALID,
                          "validator set does not hash to the header's")

    def _verify_lanes(self, block: dict, lanes: list[int],
                      keys: list[bytes]) -> None:
        c = block["commit"]
        for slot, key in zip(lanes, keys):
            _, _, time_ns, sig = c["sigs"][slot]
            if not self.verify_sig(
                    key, sign_bytes(self.chain_id, c, time_ns), sig):
                raise Refused(INVALID, f"signature in slot {slot}")

    def light_lanes(self, block: dict) -> list[int]:
        """VerifyCommitLight's lanes: the for-block slots in order, up
        to and including the first that carries the tally past 2/3 of
        the set's power."""
        vals, c = block["validators"], block["commit"]
        if len(c["sigs"]) != len(vals):
            raise Refused(INVALID, "commit size != set size")
        need = 2 * sum(p for _, p in vals)
        tally, lanes = 0, []
        for slot, (flag, _, _, _) in enumerate(c["sigs"]):
            if flag != COMMIT:
                continue
            lanes.append(slot)
            tally += vals[slot][1]
            if 3 * tally > need:
                return lanes
        raise Refused(INVALID, "own set: not more than 2/3 signed")

    def verify_commit_light(self, block: dict) -> int:
        lanes = self.light_lanes(block)
        self._verify_lanes(block, lanes,
                           [block["validators"][s][0] for s in lanes])
        return len(lanes)

    def trusting_lanes(self, trusted: dict, block: dict
                       ) -> tuple[list[int], list[bytes]]:
        """VerifyCommitLightTrusting's lanes: the for-block slots whose
        address is a TRUSTED validator's, in order, up to and including
        the first that carries the tally past the trust level of the
        trusted set's power (the slots, the trusted keys)."""
        num, den = self.trust_level
        by_addr = {canonical.address(k): (k, p)
                   for k, p in trusted["validators"]}
        need = sum(p for _, p in trusted["validators"]) * num
        tally, lanes, keys, seen = 0, [], [], set()
        for slot, (flag, addr, _, _) in enumerate(block["commit"]["sigs"]):
            if flag != COMMIT or addr not in by_addr:
                continue
            if addr in seen:
                raise Refused(INVALID, "double vote")
            seen.add(addr)
            key, power = by_addr[addr]
            lanes.append(slot)
            keys.append(key)
            tally += power
            if tally * den > need:
                return lanes, keys
        raise Refused(CANT_BE_TRUSTED, "too little trusted power signed")

    def _common(self, trusted: dict, block: dict, now_ns: int) -> None:
        """verifyNewHeaderAndVals and HeaderExpired."""
        self.validate_basic(block)
        th, h = trusted["header"], block["header"]
        if h["height"] <= th["height"]:
            raise Refused(INVALID, "height not above the trusted one")
        if th["time"] + self.period_ns <= now_ns:
            raise Refused(EXPIRED, "trusted header expired")
        if h["time"] <= th["time"]:
            raise Refused(INVALID, "time not after the trusted one")
        if h["time"] >= now_ns + self.max_clock_drift_ns:
            raise Refused(INVALID, "header from the future")

    def step(self, trusted: dict, block: dict, now_ns: int
             ) -> tuple[int, int]:
        """One verification of `block` against `trusted`: (lanes of the
        trusting check, lanes of the block's own check), 0 for a check
        that is not made; Refused otherwise. `refused_lanes` holds the
        lanes that had been selected when it refused."""
        self.refused_lanes = (0, 0)
        self._common(trusted, block, now_ns)
        if block["header"]["height"] == trusted["header"]["height"] + 1:
            # VerifyAdjacent
            if block["header"]["validators_hash"] != \
                    trusted["header"]["next_validators_hash"]:
                raise Refused(INVALID, "not the trusted next validators")
            own = self.light_lanes(block)
            self.refused_lanes = (0, len(own))
            self._verify_lanes(block, own,
                               [block["validators"][s][0] for s in own])
            return 0, len(own)
        # VerifyNonAdjacent
        lanes, keys = self.trusting_lanes(trusted, block)
        self.refused_lanes = (len(lanes), 0)
        own = self.light_lanes(block)
        self.refused_lanes = (len(lanes), len(own))
        self._verify_lanes(block, lanes, keys)
        self._verify_lanes(block, own,
                           [block["validators"][s][0] for s in own])
        return len(lanes), len(own)

    # -- verifyLightBlock --

    def begin(self, height: int) -> tuple[str, int]:
        """(case, trusted height) of a height that is not stored: at or
        above the latest trusted block FORWARD from it; below the first
        BACKWARD from it; otherwise BETWEEN, from the closest trusted
        block below."""
        hs = self._heights
        if height >= hs[-1]:
            return FORWARD, hs[-1]
        if height < hs[0]:
            return BACKWARD, hs[0]
        return BETWEEN, hs[bisect.bisect_left(hs, height) - 1]

    def verify(self, height: int, fetch, now_ns: int) -> dict:
        """The whole of one caller's verification of `height`, alone:
        {"case", "steps": [(from, to, trusting lanes, own lanes,
        verdict)], "served": hash | None, "refused": kind | None,
        "stored": heights it added}. `fetch(height)` is the primary."""
        if height in self.store:
            return {"case": "stored", "steps": [], "refused": None,
                    "served": block_hash(self.store[height]), "stored": []}
        case, base = self.begin(height)
        out = {"case": case, "steps": [], "served": None, "refused": None,
               "stored": []}
        try:
            if case == BACKWARD:
                block = self.backwards(height, fetch, now_ns)
            else:
                block = fetch(height)
                self.skipping(self.store[base], block, fetch, now_ns, out)
        except Refused as e:
            out["refused"] = e.kind
            return out
        if case == BACKWARD:
            self.trust(block)
            out["stored"].append(height)
        out["served"] = block_hash(block)
        return out

    def skipping(self, trusted: dict, target: dict, fetch, now_ns: int,
                 out: dict) -> None:
        """verifySkipping: verify what can be against the trusted block
        in hand, fetch the midpoint when too little of its power signed;
        every block verified is stored."""
        pending, seen = [target], {target["header"]["height"]}
        while pending:
            block = pending[-1]
            t_h, b_h = trusted["header"]["height"], block["header"]["height"]
            try:
                lanes = self.step(trusted, block, now_ns)
            except Refused as e:
                out["steps"].append((t_h, b_h, *self.refused_lanes, e.kind))
                if e.kind != CANT_BE_TRUSTED:
                    raise
                pivot = (t_h + b_h) // 2
                if pivot in (t_h, b_h) or pivot in seen:
                    raise
                seen.add(pivot)
                pending.append(fetch(pivot))
                continue
            out["steps"].append((t_h, b_h, *lanes, "ok"))
            self.trust(block)
            out["stored"].append(b_h)
            trusted = block
            pending.pop()

    def backwards(self, height: int, fetch, now_ns: int) -> dict:
        """backwards + VerifyBackwards: from the first trusted block
        down by hash linkage; only the target is stored."""
        cur = self.store[self._heights[0]]
        if cur["header"]["time"] + self.period_ns <= now_ns:
            raise Refused(EXPIRED, "anchor expired")
        while cur["header"]["height"] > height:
            older = fetch(cur["header"]["height"] - 1)
            link = cur["header"]["last_block_id"]
            if older["header"]["chain_id"] != self.chain_id \
                    or older["header"]["time"] >= cur["header"]["time"] \
                    or link is None or block_hash(older) != link[0]:
                raise Refused(BACKWARDS, "broken hash link")
            cur = older
        return cur
