"""The chain behind the light-proxy cell: a seeded header chain over a
validator set that moves, as plain dicts (the form
`reference/light_model.py` reads) and as the JSON bodies a node's
`/commit` and `/validators` routes hand a light client. It imports
nothing of the program; OpenSSL signs (it never decides `correct`).

Parameters (a cell's `params`): `chain_id`, `validators`, `heights`,
`power_lo` / `power_hi` (powers drawn by the seed), `move_every` (the
set moves after every such height: `leave_join` validators leave and as
many join, `reweighted` others get a new power), `absent_pct_max` (0 to
that share of a commit's signatures absent), `block_interval_s`,
`planted_every` (one height in so many has a forged first answer, the
kinds of FORGED dealt in turn).
"""

from __future__ import annotations

import base64
import hashlib
import json

import numpy as np

from benchmark import gen
from benchmark.reference import canonical
from benchmark.reference import light_model as model
from benchmark.reference.valset_model import ordered, validators_hash

# the kinds of a forged first answer, dealt in turn
FORGED = ("sig_bit", "s_plus_l", "other_block", "valset", "foreign")

_KEYS: dict = {}   # a pool worker's keys


def sign_items(seed: int, items: list) -> list[bytes]:
    """Pool worker: (purpose, key number, sign bytes) -> signatures."""
    out = []
    for purpose, i, msg in items:
        key = _KEYS.get((seed, purpose, i))
        if key is None:
            key = _KEYS[(seed, purpose, i)] = gen.private_key(
                seed, purpose, i)
        out.append(key.sign(msg))
    return out


def pool_signer(pool, seed: int):
    """A signer over gen.py's pool of processes: a list of (purpose,
    key number, sign bytes) -> signatures, in order."""
    workers = pool._max_workers

    def sign(items):
        step = max(1, -(-len(items) // (workers * 4)))
        futs = [pool.submit(sign_items, seed, items[i:i + step])
                for i in range(0, len(items), step)]
        return [s for f in futs for s in f.result()]

    return sign


def _h32(*parts) -> bytes:
    return hashlib.sha256("/".join(map(str, parts)).encode()).digest()


class Chain:
    def __init__(self, seed: int, p: dict, sign, t_end_ns: int):
        self.seed, self.p, self.chain_id = seed, p, p["chain_id"]
        rng = np.random.default_rng([seed, 0x11647])
        n, heights = p["validators"], p["heights"]
        self.pubs: dict[int, bytes] = {}      # key number -> public key
        self.number: dict[bytes, int] = {}    # public key -> key number

        def key(i: int) -> bytes:
            if i not in self.pubs:
                self.pubs[i] = gen.public_bytes(
                    gen.private_key(seed, "val", i))
                self.number[self.pubs[i]] = i
            return self.pubs[i]

        def power() -> int:
            return int(rng.integers(p["power_lo"], p["power_hi"] + 1))

        # the set in force at every height (and the one after the last)
        members = {key(i): power() for i in range(n)}
        fresh = n
        self.sets: dict[int, list] = {}
        for h in range(1, heights + 2):
            self.sets[h] = ordered(members)
            if h % p["move_every"] == 0:
                members = dict(members)
                keys = sorted(members)
                picks = rng.permutation(len(keys))
                for j in picks[:p["leave_join"]]:
                    del members[keys[j]]
                    members[key(fresh)] = power()
                    fresh += 1
                for j in picks[p["leave_join"]:
                               p["leave_join"] + p["reweighted"]]:
                    members[keys[j]] = power()
        hashes = {}
        for h, vals in self.sets.items():
            hashes[h] = hashes.get(h - 1) if h > 1 and \
                self.sets[h - 1] == vals else validators_hash(vals)
        # headers, then every signature in ONE call of the signer
        interval = int(p["block_interval_s"] * 1e9)
        self.blocks: dict[int, dict] = {}
        items, where = [], []
        last = None
        for h in range(1, heights + 1):
            vals = self.sets[h]
            header = {
                "version_block": 11, "version_app": 0,
                "chain_id": self.chain_id, "height": h,
                "time": t_end_ns - (heights - h) * interval,
                "last_block_id": last,
                "last_commit_hash": _h32(seed, "lc", h),
                "data_hash": _h32(seed, "data", h),
                "validators_hash": hashes[h],
                "next_validators_hash": hashes[h + 1],
                "consensus_hash": _h32(seed, "cons"),
                "app_hash": _h32(seed, "app", h),
                "last_results_hash": _h32(seed, "res", h),
                "evidence_hash": _h32(seed, "ev", h),
                "proposer_address": canonical.address(vals[h % len(vals)][0]),
            }
            block = {"header": header, "validators": vals}
            bhash = model.block_hash(block)
            block["commit"] = commit = {
                "height": h, "round": 0, "block_hash": bhash,
                "parts_total": 1, "parts_hash": _h32(seed, "parts", h),
                "sigs": []}
            absent = set(rng.permutation(len(vals))[:int(rng.integers(
                0, len(vals) * p["absent_pct_max"] // 100 + 1))].tolist())
            jitter = rng.integers(0, 1000, len(vals))
            for slot, (pub, _) in enumerate(vals):
                if slot in absent:
                    commit["sigs"].append((model.ABSENT, b"", 0, b""))
                    continue
                when = header["time"] + 1_000_000_000 \
                    + int(jitter[slot]) * 1_000_000
                commit["sigs"].append(
                    [model.COMMIT, canonical.address(pub), when, None])
                items.append(("val", self.number[pub],
                              model.sign_bytes(self.chain_id, commit, when)))
                where.append((h, slot))
            self.blocks[h] = block
            last = (bhash, 1, commit["parts_hash"])
        self.signed: dict[tuple, bytes] = {}   # (key, sign bytes) -> sig
        for (h, slot), item, sig in zip(where, items, sign(items)):
            entry = self.blocks[h]["commit"]["sigs"][slot]
            entry[3] = sig
            self.blocks[h]["commit"]["sigs"][slot] = tuple(entry)
            self.signed[(self.pubs[item[1]], item[2])] = sig
        self.signatures = len(items)
        # the planted heights and their forged first answers
        self.planted: dict[int, str] = {}
        every = p.get("planted_every") or 0
        for k, lo in enumerate(range(2, heights + 1, every) if every else ()):
            h = lo + int(rng.integers(0, min(every, heights + 1 - lo)))
            self.planted[h] = FORGED[k % len(FORGED)]
        self.forged = {h: self._forge(h, kind, sign)
                       for h, kind in self.planted.items()}
        self.spoiled = {(h, slot): self.forged[h]["commit"]["sigs"][slot][3]
                        for h, kind in self.planted.items()
                        if kind in ("sig_bit", "s_plus_l")
                        for slot in [self._last_own_lane(h)]}

    def _last_own_lane(self, h: int) -> int:
        """The slot of the last signature VerifyCommitLight needs."""
        return model.LightModel(self.chain_id, 1).light_lanes(
            self.blocks[h])[-1]

    def _forge(self, h: int, kind: str, sign) -> dict:
        true = self.blocks[h]
        block = {"header": dict(true["header"]),
                 "validators": list(true["validators"]),
                 "commit": dict(true["commit"],
                                sigs=list(true["commit"]["sigs"]))}
        commit = block["commit"]
        if kind in ("sig_bit", "s_plus_l"):
            slot = self._last_own_lane(h)
            flag, addr, when, sig = commit["sigs"][slot]
            commit["sigs"][slot] = (flag, addr, when, gen.corrupt(
                sig, "s_bit" if kind == "sig_bit" else "s_plus_l"))
        elif kind == "other_block":
            commit["block_hash"] = _h32(self.seed, "other", h)
        elif kind == "valset":
            pub, power = block["validators"][0]
            block["validators"][0] = (pub, power + 1)
        elif kind == "foreign":
            n = len(true["validators"])
            keys = [gen.public_bytes(gen.private_key(self.seed, "foreign", i))
                    for i in range(n)]
            vals = ordered({k: 1000 for k in keys})
            block["validators"] = vals
            block["header"]["validators_hash"] = validators_hash(vals)
            block["header"]["next_validators_hash"] = validators_hash(vals)
            commit["block_hash"] = model.block_hash(block)
            when = block["header"]["time"] + 1_000_000_000
            msg = model.sign_bytes(self.chain_id, commit, when)
            sigs = sign([("foreign", keys.index(k), msg) for k, _ in vals])
            commit["sigs"] = [(model.COMMIT, canonical.address(k), when, s)
                              for (k, _), s in zip(vals, sigs)]
            for (k, _), s in zip(vals, sigs):
                self.signed[(k, msg)] = s
        else:
            raise ValueError(kind)
        return block

    @property
    def top(self) -> int:
        return self.p["heights"]


# ------------------------------------------------------- the wire bodies


def _hex(b: bytes) -> str:
    return b.hex().upper()


def _bid(block_hash: bytes, total: int, parts_hash: bytes) -> dict:
    return {"hash": _hex(block_hash),
            "parts": {"total": total, "hash": _hex(parts_hash)}}


def commit_body(block: dict) -> str:
    """What `/commit?height=h` answers, as text."""
    h, c = block["header"], block["commit"]
    header = {
        "version": {"block": h["version_block"], "app": h["version_app"]},
        "chain_id": h["chain_id"], "height": str(h["height"]),
        "time": str(h["time"]),
        "last_block_id": _bid(*h["last_block_id"]) if h["last_block_id"]
        else {"hash": "", "parts": {"total": 0, "hash": ""}},
    }
    header.update({k: _hex(h[k]) for k in (
        "last_commit_hash", "data_hash", "validators_hash",
        "next_validators_hash", "consensus_hash", "app_hash",
        "last_results_hash", "evidence_hash", "proposer_address")})
    commit = {
        "height": str(c["height"]), "round": c["round"],
        "block_id": _bid(c["block_hash"], c["parts_total"], c["parts_hash"]),
        "signatures": [{
            "block_id_flag": flag, "validator_address": _hex(addr),
            "timestamp": str(when),
            "signature": base64.b64encode(sig).decode()}
            for flag, addr, when, sig in c["sigs"]]}
    return json.dumps({"signed_header": {"header": header, "commit": commit},
                       "canonical": True})


def validators_bodies(block: dict, per_page: int = 100) -> list[str]:
    """What `/validators?height=h&page=k&per_page=100` answers, a page
    an entry."""
    vals = block["validators"]
    rows = [{"address": _hex(canonical.address(pub)),
             "pub_key": {"type": "ed25519",
                         "value": base64.b64encode(pub).decode()},
             "voting_power": str(power), "proposer_priority": "0"}
            for pub, power in vals]
    return [json.dumps({"block_height": str(block["header"]["height"]),
                        "validators": rows[lo:lo + per_page],
                        "count": str(len(rows[lo:lo + per_page])),
                        "total": str(len(rows))})
            for lo in range(0, len(rows), per_page)]


def reply_hash(reply: dict) -> bytes:
    """The header hash of a proxy's `commit` reply, by the reference."""
    h = reply["signed_header"]["header"]
    lb = h["last_block_id"]
    header = {
        "version_block": int(h["version"]["block"]),
        "version_app": int(h["version"]["app"]),
        "chain_id": h["chain_id"], "height": int(h["height"]),
        "time": int(h["time"]),
        "last_block_id": (bytes.fromhex(lb["hash"]), int(lb["parts"]["total"]),
                          bytes.fromhex(lb["parts"]["hash"]))
        if lb["hash"] else None}
    header.update({k: bytes.fromhex(h[k]) for k in (
        "last_commit_hash", "data_hash", "validators_hash",
        "next_validators_hash", "consensus_hash", "app_hash",
        "last_results_hash", "evidence_hash", "proposer_address")})
    return model.header_hash(header)
