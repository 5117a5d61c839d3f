"""Traffic: a kvstore chain whose validator set holds ed25519 and
sr25519 keys side by side and whose blocks carry a thousand
DuplicateVoteEvidence, made from the seed outside the window and
replayed through the program's own p2p-free fast-sync loop
(`blockchain/verify_ahead.py` `sync_window`) with the block executor
built as the node builds it: WITH an evidence pool on sqlite
(`node/__init__.py`; without one `validate_block` skips evidence).

Parameters (the cell's file), beside the still-set driver's:
`ed25519` / `sr25519` (how many validators hold which key type; WHICH
is the seed's), `power_band` ("lo-hi"), `window_blocks` (must be the
program's BATCH_WINDOW), `evidence_blocks` (heights of the blocks that
carry evidence), `evidence_validators` / `evidence_ed25519` (an
operator's validators that double-sign, and how many of them hold
ed25519 keys), `evidence_heights` (consecutive heights of an incident)
and `evidence_lead` (an incident begins that many heights before the
block that commits it). Each of those validators signs two prevotes and
two precommits at each height of the incident, for the chain's block
and for another the operator's second instance saw:
`evidence_validators` x `evidence_heights` x 2 evidence a block.

Two faults are planted, one a replay, in turn. Even replays: the
commit of height `blocks` - 1 carries a bad signature in an sr25519
lane; the window refuses that height and the replay ends one below.
Odd replays: the block one below that carries one more list of
evidence whose item `planted_evidence_item` has a bad sr25519
signature on vote B; `apply_block` refuses that block for that
evidence. A replay then starts again from height 1 into fresh stores.
Where a replay takes most of the window the odd one never ends inside
it, so the planted evidence is also met OUTSIDE the window: the warm
replay ends with a probe, one more list (the first incident's votes
against another fork, vote B of the same item spoiled) handed to its
live pool as a proposed block's evidence, which `Pool.check_evidence`
must refuse for that item. The timed window holds the replays alone:
what a pass leaves for the check (the pool's committed marks, a few of
them proposed again) is read after the window's last replay has ended.

sr25519 signatures are made here, in bulk (`SrSigner`): a batch's nonce
points by running addition, the challenges through the program's
vectorised Merlin. A signer decides nothing: a sample of what it signed
is verified by the plain reference, one at a time.
"""

from __future__ import annotations

import asyncio
import contextlib
import gc
import hashlib
import os
import shutil
import threading
import time

import numpy as np

from benchmark import gen
from benchmark.harness import BenchFailure, say
from benchmark.reference import canonical
from benchmark.reference import ed25519_zip215 as ed_ref
from benchmark.reference import evidence_model as em
from benchmark.reference import sr25519_schnorrkel as sr_ref
from benchmark.reference import valset_model as vm
from benchmark.reference.kvstore_model import KVStoreModel
from benchmark.traffic import fastsync_replay
from benchmark.traffic.fastsync_churn_replay import NAMED, lane_bucket
from benchmark.traffic.fastsync_replay import CHAIN_ID, GENESIS_TIME

SAMPLED_LANES = 512        # at least, of each replay set
SAMPLED_EACH = 128         # at least, of each key type and of each site
REPROPOSED = 8
HOST_BACKENDS = ("host", "host-sr25519", "host-secp256k1",
                 "cpu-jit-sr25519")   # crypto_batch_lanes' labels
_COMMITTED = b"\x01"       # evidence/pool.go's prefix of committed marks


# ------------------------------------------------------------ the signers

_KEYS: dict = {}   # a pool worker's ed25519 keys, by (seed, number)


def sign_msgs(seed: int, numbers: list[int], msgs: list[bytes]) -> bytes:
    """Pool worker: the ed25519 signatures of validators `numbers`
    (`gen.private_key(seed, "val", number)`) over msgs[i], joined."""
    out = []
    for number, msg in zip(numbers, msgs):
        key = _KEYS.get((seed, number))
        if key is None:
            key = _KEYS[(seed, number)] = gen.private_key(
                seed, "val", number)
        out.append(key.sign(msg))
    return b"".join(out)


class SrSigner:
    """sr25519 signatures in bulk. A batch's nonces are r0 + i * delta,
    so their points are one addition each from the last; the public
    keys and nonce points are encoded by the plain reference
    (`sr25519_schnorrkel.encode`); the challenges come from the
    program's lane-vectorised Merlin, one call a batch. Such nonces
    protect nothing: this is test traffic, and what it signs is checked
    by the reference verifier."""

    def __init__(self, seed: int, minis: dict[int, bytes]):
        self.scalars = {i: sr_ref.expand_mini(m)[0] for i, m in minis.items()}
        self.pubs = {i: sr_ref.encode(ed_ref.scalar_mult(k, ed_ref._B_PT))
                     for i, k in self.scalars.items()}
        self._tag = f"bench/sr-nonce/{seed}/".encode()
        self._delta = int.from_bytes(hashlib.sha512(
            self._tag + b"delta").digest(), "little") % sr_ref.L
        self._delta_pt = ed_ref.scalar_mult(self._delta, ed_ref._B_PT)

    def sign(self, numbers: list[int], msgs: list[bytes],
             purpose: str) -> list[bytes]:
        from tendermint_tpu.crypto.merlin_batch import sr25519_challenges

        n = len(numbers)
        if not n:
            return []
        r = int.from_bytes(hashlib.sha512(
            self._tag + purpose.encode()).digest(), "little") % sr_ref.L
        pt = ed_ref.scalar_mult(r, ed_ref._B_PT)
        nonces, r_rows = [], []
        for _ in range(n):
            nonces.append(r)
            r_rows.append(sr_ref.encode(pt))
            r = (r + self._delta) % sr_ref.L
            pt = ed_ref.pt_add(pt, self._delta_pt)
        pubs = np.frombuffer(b"".join(self.pubs[i] for i in numbers),
                             np.uint8).reshape(n, 32)
        rs = np.frombuffer(b"".join(r_rows), np.uint8).reshape(n, 32)
        ks = sr25519_challenges(pubs, msgs, rs)
        out = []
        for i, number in enumerate(numbers):
            s = (int(ks[i]) * self.scalars[number] + nonces[i]) % sr_ref.L
            sig = bytearray(r_rows[i] + s.to_bytes(32, "little"))
            sig[63] |= 0x80
            out.append(bytes(sig))
        return out


def _prevote(pre: bytes) -> bytes:
    """canonical.vote_sign_parts gives a precommit's bytes before the
    timestamp: the same with the vote's type field set to prevote."""
    assert pre[:2] == b"\x08\x02"
    return b"\x08\x01" + pre[2:]


class Driver(fastsync_replay.Driver):
    CONTROLS = ("skips_commit_check", "stale_app_state", "ed25519_only",
                "skips_evidence_signatures", "evidence_first_vote_only")

    def __init__(self, run):
        super().__init__(run)
        from tendermint_tpu.libs import tracing

        # a program that checks a block's evidence one signature at a
        # time on the host is not the path anybody will run
        if "evidence.check" not in tracing.registered_kinds():
            raise BenchFailure(
                "the program has no batched evidence check "
                "(no span kind evidence.check)")
        from tendermint_tpu.blockchain.verify_ahead import sync_window

        self.sync_window = sync_window
        self._host_lanes = self._host_span_lanes = 0
        self._hashes = {}    # id(evidence) -> its hash, as the program has it

    # ------------------------------------------------------- the chain

    def _plan(self) -> None:
        from tendermint_tpu.blockchain.verify_ahead import BATCH_WINDOW

        p = self.run.params
        if p["window_blocks"] != BATCH_WINDOW:
            raise BenchFailure(
                f"the cell is cut for windows of {p['window_blocks']} "
                f"blocks, the program's are {BATCH_WINDOW}")
        self.length = p["blocks"]
        self.bad = self.length - 1    # block `length` carries its commit
        self.end = self.bad - 1       # an even replay's last height
        self.ev_blocks = list(p["evidence_blocks"])
        lead, span = p["evidence_lead"], p["evidence_heights"]
        # block -> the heights of the incident it commits; the odd
        # replays' extra list rides in block `end`
        self.incident = {b: list(range(b - lead, b - lead + span))
                         for b in self.ev_blocks + [self.end]}
        # the probe: the first incident's heights once more
        self.incident["probe"] = self.incident[self.ev_blocks[0]]
        if any(hs[0] < 1 or hs[-1] >= b for b, hs in self.incident.items()
               if b != "probe") or self.end in self.ev_blocks:
            raise BenchFailure(f"evidence blocks {self.ev_blocks} do not "
                               f"fit a chain of {self.length}")

    def setup(self) -> None:
        from tendermint_tpu.abci import types as abci_t
        from tendermint_tpu.abci.kvstore import KVStoreApp
        from tendermint_tpu.crypto.ed25519 import Ed25519PubKey
        from tendermint_tpu.crypto.sr25519 import Sr25519PubKey
        from tendermint_tpu.state import make_genesis_state
        from tendermint_tpu.state.execution import update_state
        from tendermint_tpu.types.block import BlockID
        from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
        from tendermint_tpu.types.params import (
            ConsensusParams, ValidatorParams)

        run, n, p = self.run, self.n, self.run.params
        t0 = time.perf_counter()
        # set-up builds millions of objects that stay: keep the
        # collector from walking them under every kernel trace
        # (run.py collects and freezes once warm() is done)
        gc.disable()
        shutil.rmtree(self.dir, ignore_errors=True)
        self._plan()
        n_sr = p["sr25519"]
        if p["ed25519"] + n_sr != n:
            raise BenchFailure("ed25519 + sr25519 != validators")
        rng = run.rng("mixed")
        lo, hi = (int(x) for x in p["power_band"].split("-"))
        is_sr = np.zeros(n, bool)
        is_sr[rng.choice(n, n_sr, replace=False)] = True
        powers = [int(x) for x in rng.integers(lo, hi + 1, n)]
        self.signer = SrSigner(run.seed, {
            i: gen.key_seed(run.seed, "sr", i) for i in np.flatnonzero(is_sr)})
        # public key -> (key type, number of its key, power)
        self.info = {}
        for i in range(n):
            if is_sr[i]:
                self.info[self.signer.pubs[i]] = ("sr25519", i, powers[i])
            else:
                self.info[gen.public_bytes(gen.private_key(
                    run.seed, "val", i))] = ("ed25519", i, powers[i])
        self.order = vm.ordered({pk: v[2] for pk, v in self.info.items()})
        self.kinds = [self.info[pk][0] for pk, _ in self.order]
        self.addrs = [canonical.address(pk) for pk, _ in self.order]
        params = ConsensusParams(validator=ValidatorParams(
            pub_key_types=list(run.config["consensus_params"]["validator"]
                               ["pub_key_types"])))
        key_of = {"ed25519": Ed25519PubKey, "sr25519": Sr25519PubKey}
        self.gdoc = GenesisDoc(
            chain_id=CHAIN_ID, genesis_time=GENESIS_TIME,
            consensus_params=params,
            validators=[GenesisValidator(key_of[k](pk), power)
                        for pk, (k, _, power) in self.info.items()])
        self.gdoc.validate_and_complete()
        state = make_genesis_state(self.gdoc)
        if [(v.pub_key.bytes(), v.voting_power)
                for v in state.validators.validators] != self.order:
            raise BenchFailure("the program orders the genesis set "
                               "differently from the reference")
        self.max_age = (params.evidence.max_age_num_blocks,
                        params.evidence.max_age_duration_ns)
        # the ed25519 keys' tables build on the device meanwhile
        warm = state.validators.warm_device_tables()
        self._draw_operator(rng)
        self._check_shapes()

        app, kv = KVStoreApp(), KVStoreModel()
        self.blocks, self.expected = [], []
        self.commits = {}       # height -> (pre, suf, times, sigs)
        self.block_ids, self.block_times = {}, {}
        self.lists = {}     # (block, planted?) -> (the program's, the model's)
        self.pool = gen.make_pool()
        last_commit = signers_times = None
        for h in range(1, self.length + 1):
            txs = [self._tx(h, k) for k in range(p["txs_per_block"])]
            if h == 1:
                when = state.last_block_time
            else:   # BFT time: the weighted median of the votes
                when = vm.weighted_median(list(zip(
                    signers_times, (pw for _, pw in self.order))))
            self.block_times[h] = when
            evidence = self._evidence_of(h) if h in self.ev_blocks else []
            proposer = state.validators.get_proposer().address
            block = state.make_block(h, txs, last_commit, evidence,
                                     proposer, when)
            parts = block.make_part_set()
            bid = BlockID(block.hash(), parts.header())
            self.block_ids[h] = em.BlockId(
                block.hash(), parts.header().total, parts.header().hash)
            self.blocks.append(block)
            app.begin_block(abci_t.RequestBeginBlock())
            responses = {
                "deliver_txs": [app.deliver_tx(abci_t.RequestDeliverTx(tx))
                                for tx in txs],
                "end_block": app.end_block(abci_t.RequestEndBlock(h))}
            before = state
            state = update_state(state, bid, block, responses, [])
            state.app_hash = app.commit(abci_t.RequestCommit()).data
            for tx in txs:
                kv.deliver(tx)
            self.expected.append(kv.app_hash())
            last_commit, signers_times = self._commit(
                h, bid, when, plant=h == self.bad)
            if h == max(p["window_blocks"] + 1, self.ev_blocks[0]):
                self._start_warmers()
            if h == self.end:
                # the odd replays' branch: the same block with one more
                # list of evidence, and the block that carries ITS commit
                other = before.make_block(h, txs, block.last_commit,
                                          self._evidence_of(h, plant=True),
                                          proposer, when)
                oparts = other.make_part_set()
                obid = BlockID(other.hash(), oparts.header())
                ostate = update_state(before, obid, other, responses, [])
                ostate.app_hash = state.app_hash
                ocommit, otimes = self._commit(h, obid, when, keep=False)
                after = ostate.make_block(
                    h + 1, [self._tx(h + 1, k)
                            for k in range(p["txs_per_block"])],
                    ocommit, [], ostate.validators.get_proposer().address,
                    vm.weighted_median(list(zip(
                        otimes, (pw for _, pw in self.order)))))
                self.odd_blocks = self.blocks[:-1] + [other, after]
        self._evidence_of("probe", plant=True)
        self.pool.shutdown(wait=True)
        self.model = em.EvidenceModel(
            CHAIN_ID, _Same({a: (k, pk, pw) for a, k, (pk, pw) in zip(
                self.addrs, self.kinds, self.order)}),
            self.block_times, self.max_age)
        if warm is not None:
            warm.join()
        say("chain ready", validators=n, sr25519=n_sr, blocks=self.length,
            evidence_blocks=self.ev_blocks,
            evidence_per_block=len(self.lists[(self.ev_blocks[0], False)][0]),
            evidence_bytes=sum(len(e.to_bytes())
                               for e in self.lists[(self.ev_blocks[0], False)][0]),
            planted_commit=self.refusal_even, planted_evidence=(
                self.end, self.planted_item), shapes=self.shapes,
            seconds=round(time.perf_counter() - t0, 3))

    def _draw_operator(self, rng) -> None:
        """The operator's validators (positions in the set's order):
        its sr25519 ones first, so that the list's planted item, the
        first of a (height, type) group, holds an sr25519 key."""
        p = self.run.params
        n_ed = p["evidence_ed25519"]
        n_sr = p["evidence_validators"] - n_ed
        ed = [i for i, k in enumerate(self.kinds) if k == "ed25519"]
        sr = [i for i, k in enumerate(self.kinds) if k == "sr25519"]
        self.operator = sorted(rng.choice(sr, n_sr, replace=False).tolist()) \
            + sorted(rng.choice(ed, n_ed, replace=False).tolist())
        self.planted_item = p["planted_evidence_item"]
        if self.planted_item % len(self.operator) >= n_sr:
            raise BenchFailure(
                f"item {self.planted_item} of a list holds no sr25519 key")

    def _check_shapes(self) -> None:
        """The lanes of each launch site, by key type, and the launch
        shape they fall into: the set stands still and every validator
        signs, so each site has one of each."""
        light = vm.light_lanes(self.order)
        p = self.run.params
        ed_all = self.kinds.count("ed25519")
        ed_light = self.kinds[:light].count("ed25519")
        w = p["window_blocks"]
        per_list = p["evidence_heights"] * 4
        ev_ed = p["evidence_ed25519"] * per_list
        ev_sr = (p["evidence_validators"] - p["evidence_ed25519"]) * per_list
        lanes = {"window": (w * ed_light, w * (light - ed_light)),
                 "last_commit": (ed_all, self.n - ed_all),
                 "one_commit_light": (ed_light, light - ed_light),
                 "evidence": (ev_ed, ev_sr)}
        self.shapes = {site: {"ed25519": [e, lane_bucket(e)],
                              "sr25519": [s, lane_bucket(s)]}
                       for site, (e, s) in lanes.items()}
        self.light = light

    def _start_warmers(self) -> None:
        """Every launch site's two shapes, each site in a thread of
        its own, begun as soon as the chain holds a whole window and a
        list of evidence: the compiles (or cache loads) run beside one
        another and beside the rest of the chain's making, where the
        warm replay alone would meet them one after the other."""
        from tendermint_tpu.blockchain import verify_ahead
        from tendermint_tpu.evidence.verify import signature_errors
        from tendermint_tpu.state import make_genesis_state

        vals = make_genesis_state(self.gdoc).validators
        w = self.run.params["window_blocks"]
        window = self.blocks[:w + 1]
        second = self.blocks[1]
        commit = (CHAIN_ID, second.header.last_block_id, 1,
                  second.last_commit)
        evidence = [(ev, ev.vote_a.validator_index)
                    for ev in self.lists[(self.ev_blocks[0], False)][0]]
        sites = {
            "window": lambda: verify_ahead._batch_verify_window(
                vals, CHAIN_ID, verify_ahead.window_items(window)[0]),
            "last_commit": lambda: vals.verify_commit(*commit),
            "one_commit_light": lambda: vals.verify_commit_light(*commit),
            "evidence": lambda: signature_errors(CHAIN_ID, vals, evidence),
        }
        self._warmers = [threading.Thread(target=fn, name=f"warm-{site}")
                         for site, fn in sites.items()]
        for t in self._warmers:
            t.start()

    def _sign(self, positions: list[int], msgs: list[bytes],
              purpose: str) -> list[bytes]:
        """Signatures of the validators at `positions` of the set's
        order over msgs[i]: ed25519 in the pool, sr25519 here
        meanwhile."""
        ed = [j for j, i in enumerate(positions)
              if self.kinds[i] == "ed25519"]
        sr = [j for j, i in enumerate(positions)
              if self.kinds[i] == "sr25519"]
        number = [self.info[self.order[i][0]][1] for i in positions]
        step = max(1, -(-len(ed) // self.pool._max_workers))
        futs = [self.pool.submit(
            sign_msgs, self.run.seed, [number[j] for j in ed[at:at + step]],
            [msgs[j] for j in ed[at:at + step]])
            for at in range(0, len(ed), step)]
        out = [b""] * len(positions)
        for j, sig in zip(sr, self.signer.sign(
                [number[j] for j in sr], [msgs[j] for j in sr], purpose)):
            out[j] = sig
        raw = b"".join(f.result() for f in futs)
        for k, j in enumerate(ed):
            out[j] = raw[64 * k:64 * k + 64]
        return out

    def _commit(self, h: int, bid, when: int, plant: bool = False,
                keep: bool = True):
        """Every validator's precommit for block `h`; returns the
        Commit and the votes' times."""
        from tendermint_tpu.types.block import BlockIDFlag, Commit, CommitSig

        psh = bid.part_set_header
        pre, suf = canonical.vote_sign_parts(
            CHAIN_ID, h, 0, bid.hash, psh.total, psh.hash)
        times = [when + 1_000_000_000 + i * 1_000 for i in range(self.n)]
        sigs = self._sign(
            list(range(self.n)),
            [canonical.with_timestamp(pre, suf, t) for t in times],
            f"commit/{h}/{bid.hash.hex()}")
        if plant:
            # an sr25519 lane among those a light check reads
            lane = self.kinds[:self.light // 2].index("sr25519")
            sigs[lane] = gen.corrupt(sigs[lane], "s_bit")
            self.refusal_even = ("commit", h, [lane])
        if keep:
            self.commits[h] = (pre, suf, times, sigs)
        return Commit(h, 0, bid, [
            CommitSig(BlockIDFlag.COMMIT, self.addrs[i], times[i], sigs[i])
            for i in range(self.n)]), times

    def _evidence_of(self, block, plant: bool = False) -> list:
        """The evidence block `block` commits: at each height of its
        incident every validator of the operator signed two prevotes
        and two precommits, for the chain's block and for another. In
        list order: height, type, validator. The program's objects go
        into the block, the model's beside them."""
        from tendermint_tpu.types.block import BlockID, PartSetHeader
        from tendermint_tpu.types.evidence import DuplicateVoteEvidence
        from tendermint_tpu.types.vote import Vote

        total = sum(pw for _, pw in self.order)
        slots = []   # (height, type, position, block id, time)
        for h in self.incident[block]:
            real = self.block_ids[h]
            tag = b"probe/" if block == "probe" else b"fork/"
            fork = em.BlockId(
                hashlib.sha256(tag + real.hash).digest(), 1,
                hashlib.sha256(tag + b"parts/" + real.hash).digest())
            for vtype in (em.PREVOTE, em.PRECOMMIT):
                for i in self.operator:
                    for bid in sorted((real, fork), key=em.BlockId.key):
                        slots.append((h, vtype, i, bid,
                                      self.block_times[h] + 500_000_000
                                      + vtype * 1_000_000 + i * 1_000))
        msgs = []
        for h, vtype, _, bid, when in slots:
            pre, suf = canonical.vote_sign_parts(
                CHAIN_ID, h, 0, bid.hash, bid.parts_total, bid.parts_hash)
            msgs.append(canonical.with_timestamp(
                _prevote(pre) if vtype == em.PREVOTE else pre, suf, when))
        sigs = self._sign([s[2] for s in slots], msgs,
                          f"evidence/{block}/{int(plant)}")
        if plant:    # vote B of the planted item
            at = 2 * self.planted_item + 1
            sigs[at] = gen.corrupt(sigs[at], "s_bit")
        mine, model = [], []
        for k in range(0, len(slots), 2):
            votes, plain = [], []
            for (h, vtype, i, bid, when), sig in zip(slots[k:k + 2],
                                                     sigs[k:k + 2]):
                votes.append(Vote(
                    type=vtype, height=h, round=0, block_id=BlockID(
                        bid.hash, PartSetHeader(bid.parts_total,
                                                bid.parts_hash)),
                    timestamp=when, validator_address=self.addrs[i],
                    validator_index=i, signature=sig))
                plain.append(em.Vote(vtype, h, 0, bid, when,
                                     self.addrs[i], sig))
            h, i = slots[k][0], slots[k][2]
            power = self.order[i][1]
            ev = DuplicateVoteEvidence(votes[0], votes[1], total, power,
                                       self.block_times[h])
            try:
                ev.validate_basic()
            except ValueError as e:
                raise BenchFailure(f"the program refuses the form of the "
                                   f"reference's evidence: {e}")
            mine.append(ev)
            model.append(em.DuplicateVote(plain[0], plain[1], total, power,
                                          self.block_times[h]))
        self.lists[(block, plant)] = (mine, model)
        return mine

    # ------------------------------------------------------ one replay

    async def _replay(self, deadline: float | None, odd: bool) -> dict:
        """Blocks 1.. through the program's sync loop into fresh
        stores, until a refusal (by a window or by apply_block) or the
        first window's end past `deadline`. Its stores stay open and
        its pool stays live until `out["after"]()` is called, outside
        every clock: that reads what the check needs of them."""
        from tendermint_tpu.abci import types as abci_t
        from tendermint_tpu.abci.client import LocalClient
        from tendermint_tpu.abci.kvstore import PersistentKVStoreApp
        from tendermint_tpu.blockchain.verify_ahead import (
            BATCH_WINDOW, WindowPipeline)
        from tendermint_tpu.config import Config
        from tendermint_tpu.evidence import Pool
        from tendermint_tpu.libs.db import SqliteDB
        from tendermint_tpu.state import make_genesis_state
        from tendermint_tpu.state.execution import BlockExecutor
        from tendermint_tpu.state.store import Store
        from tendermint_tpu.store import BlockStore
        from tendermint_tpu.types.validator_set import VerificationError

        run = self.run
        t_begin = time.perf_counter()
        self._pass_no += 1
        sync = Config().base.db_synchronous   # the node's default
        chain = self.odd_blocks if odd else self.blocks
        home = os.path.join(self.dir, f"p{self._pass_no}")

        def db(name):
            return SqliteDB(os.path.join(home, name + ".sqlite"),
                            synchronous=sync)

        dbs = [db("state"), db("blockstore"), db("app"), db("evidence")]
        state = make_genesis_state(self.gdoc)
        store = Store(dbs[0])
        store.save(state)
        block_store = BlockStore(dbs[1])
        app = PersistentKVStoreApp(dbs[2])
        app.init_chain(abci_t.RequestInitChain(validators=[
            abci_t.ValidatorUpdate(v.pub_key.type_name, v.pub_key.bytes(),
                                   v.voting_power)
            for v in state.validators.validators]))
        client = LocalClient(app)
        await client.start()
        # as node/__init__.py builds them: the pool on its own db,
        # handed to the executor
        evpool = Pool(dbs[3], store, block_store)
        executor = BlockExecutor(store, client, evidence_pool=evpool)
        pipeline = WindowPipeline()
        pos = 0
        out = {"odd": odd, "applied": 0, "sigs": 0, "refusals": [],
               "evidence_blocks": [], "ended": "chain", "ed_lanes": 0}
        ed_lanes = {site: v["ed25519"][0] for site, v in self.shapes.items()}
        clock = contextlib.ExitStack()   # the harness span that is open
        waits, applies = [0.0, 0.0], [0.0, 0.0]

        def lap(into):
            nonlocal since
            now = time.perf_counter()
            into[0] += now - since
            into[1] = max(into[1], now - since)
            since = now

        def peek(k):
            return chain[pos:pos + k]

        def before_block(block):
            clock.close()
            lap(waits)
            clock.enter_context(run.span("apply_block"))
            # what validate_block will put on the tables
            if block.last_commit is not None:
                out["ed_lanes"] += ed_lanes["last_commit"]
            if block.evidence.evidence:
                out["ed_lanes"] += ed_lanes["evidence"]

        def after_block(new_state, block):
            nonlocal pos, state
            clock.close()
            lap(applies)
            state = new_state   # held here too: a refusal by
            pos += 1            # apply_block leaves sync_window raising
            out["applied"] += 1
            out["sigs"] += self.n + 2 * len(block.evidence.evidence)
            if block.evidence.evidence:
                out["evidence_blocks"].append(block.header.height)

        since = t_loop = time.perf_counter()
        try:
            while True:
                if deadline is not None and time.perf_counter() >= deadline:
                    out["ended"] = "cut"
                    break
                window = peek(BATCH_WINDOW + 1)
                if len(window) < 2:
                    break
                clock.enter_context(run.span("await_window"))
                out["ed_lanes"] += (len(window) - 1) \
                    * ed_lanes["one_commit_light"]
                try:
                    state, _, refused = await self.sync_window(
                        pipeline, state, window, peek, block_store,
                        executor, before_block, after_block)
                except Exception as e:
                    # apply_block refused the block below `pos` + 1
                    # (the reference's node stops here)
                    out["refusals"].append((
                        "evidence", pos + 1,
                        getattr(e, "evidence_index", None), str(e)))
                    out["ended"] = "refused"
                    break
                finally:
                    clock.close()
                run.ledger.drain()
                if refused is None:
                    continue
                # what an operator asks of a refused commit: which
                # signature, by its index in the set
                commit = window[refused.index + 1].last_commit
                named = None
                try:
                    state.validators.verify_commit_light(
                        CHAIN_ID, commit.block_id, refused.height, commit)
                except VerificationError as e:
                    if str(e).startswith(NAMED):
                        named = [int(x) for x in
                                 str(e)[len(NAMED):-1].split(",")]
                out["ed_lanes"] += ed_lanes["one_commit_light"]
                out["refusals"].append(("commit", refused.height, named))
                out["ended"] = "refused"
                break
            out["loop_end"] = time.perf_counter()
            out["seconds"] = {
                "all": round(out["loop_end"] - t_begin, 3),
                "fresh_stores": round(t_loop - t_begin, 3),
                "await_windows": [round(x, 3) for x in waits],
                "apply_blocks": [round(x, 3) for x in applies]}
        finally:
            clock.close()
            pf = pipeline._prefetch
            if pf is not None:  # let an in-flight window land
                await asyncio.wait([pf[1]])
                out["ed_lanes"] += (len(pf[2]) - 1) \
                    * ed_lanes["one_commit_light"]
            await client.stop()
        out["height"] = state.last_block_height
        out["app_hash"] = state.app_hash
        out["app_height"] = app.height
        out["store_height"] = block_store.height

        def after(probe: bool = False) -> None:
            """What the pool holds as committed, whether it would take
            some of it again and, of the warm replay, the probe."""
            try:
                out["committed"] = {bytes(k[1:]) for k, _ in
                                    dbs[3].iterate_prefix(_COMMITTED)}
                out["reproposed_accepted"] = self._repropose(evpool, out)
                if probe:
                    try:
                        evpool.check_evidence(
                            self.lists[("probe", True)][0])
                        out["probe"] = None
                    except Exception as e:
                        out["probe"] = (getattr(e, "evidence_index", None),
                                        str(e))
            finally:
                for d in dbs:
                    d.close()
                shutil.rmtree(home, ignore_errors=True)

        out["after"] = after
        return out

    def _repropose(self, evpool, out) -> int:
        """Of a sample of the evidence this replay committed, how many
        the pool would take in a block again (none may be)."""
        taken = 0
        for b in out["evidence_blocks"][:1]:
            mine = self.lists[self._applied(b)][0]
            rng = self.run.rng(f"again{b}")
            for i in rng.choice(len(mine), min(REPROPOSED, len(mine)),
                                replace=False):
                try:
                    evpool.check_evidence([mine[i]])
                    taken += 1
                except Exception as e:
                    taken += "already committed" not in str(e)
        return taken

    def warm(self) -> None:
        """Every shape a replay launches, of both kernels: the threads
        `_start_warmers` began, then a whole even replay (the odd one's
        tail adds none: its extra list has the lanes of the other
        four), which must end as planted."""
        t0 = time.perf_counter()
        for t in self._warmers:
            t.join()
        shapes_s = time.perf_counter() - t0
        try:
            out = asyncio.run(self._replay(None, odd=False))
            # the planted evidence, met here whatever the window holds
            out.pop("after")(probe=True)
            self.warm_probe = (out["height"], out["probe"])
        finally:
            gc.enable()
        if (out["applied"], out["refusals"]) != (self.end,
                                                 [self.refusal_even]):
            raise BenchFailure(
                f"the warm replay applied {out['applied']} blocks and "
                f"refused {out['refusals']}: not {self.end} and "
                f"{self.refusal_even}")
        say("warm replay", applied=out["applied"],
            refusals=out["refusals"], waited_for_shapes_s=round(shapes_s, 3),
            seconds=round(time.perf_counter() - t0, 3))

    @staticmethod
    def _host_lanes_now() -> float:
        from tendermint_tpu.libs.metrics import crypto_metrics

        lanes = crypto_metrics().batch_lanes
        return sum(lanes.value(backend=b) for b in HOST_BACKENDS)

    def measure(self, seconds: float) -> dict:
        """Replays, even and odd in turn, until the first window's end
        past `seconds`; the rate is over the time to that end."""
        from tendermint_tpu.libs import tracing
        from tendermint_tpu.libs.tracing import TRACER

        run = self.run
        host0 = self._host_lanes_now()
        t0 = time.perf_counter()
        deadline = t0 + seconds
        if run.trace:
            # where the harness's profiler slice begins (TraceSlice:
            # the middle of the window), on the launch ledger's clock:
            # for the reader that needs the lanes of the slice's launches
            slice_s = min(run.params.get("trace_slice_s", 2.0), seconds)
            run.counters["trace_slice_from_mono"] = \
                time.monotonic() + (seconds - slice_s) / 2
        while time.perf_counter() < deadline:
            self.passes.append(asyncio.run(self._replay(
                deadline, odd=len(self.passes) % 2 == 1)))
        t1 = self.passes[-1]["loop_end"]
        run.ledger.drain()
        for p in self.passes:   # outside the clock
            p.pop("after")()
        # lanes a verifier of the program took on the host: by its
        # counter (nothing is lost) and by its spans (the ring's)
        self._host_lanes = self._host_lanes_now() - host0
        self._host_span_lanes = sum(
            (r[6] or {}).get("lanes", 0) for r in TRACER.snapshot()
            if r[0] == tracing.CRYPTO_HOST_VERIFY)
        applied = sum(p["applied"] for p in self.passes)
        sigs = sum(p["sigs"] for p in self.passes)
        run.counters["blocks_applied"] = applied
        # ed25519 lanes the replays handed to the verify sites: every
        # one of them should ride the tables (table_lane_share.mixed)
        run.counters["ed25519_lanes_attempted"] = sum(
            p["ed_lanes"] for p in self.passes)
        say("window", passes=len(self.passes), blocks_applied=applied,
            evidence_blocks=[p["evidence_blocks"] for p in self.passes],
            blocks_per_s=applied / (t1 - t0), seconds=t1 - t0,
            by_pass=[dict(p["seconds"], applied=p["applied"],
                          odd=p["odd"]) for p in self.passes])
        return {"attempted": applied, "failed": 0,
                "metrics": {"sigs_per_s": sigs / (t1 - t0)}}

    # ---------------------------------------------------------- check

    def check(self, control: str | None = None) -> dict:
        """Every replay of the window against the models and the plain
        verifiers (benchmark/README-mixed.md)."""
        weak = control if control in ("skips_evidence_signatures",
                                      "evidence_first_vote_only") else None
        wave_sr = control == "ed25519_only"
        reached = max((p["height"] for p in self.passes), default=0)
        lanes = self._sample(reached)
        wrong_lists, odd_verdict, probe_verdict = self._model_verdicts(
            lanes, weak)
        wrong_probe = int(self.warm_probe[1] != probe_verdict)
        wrong_refusal = wrong_height = wrong_hash = 0
        wrong_marks = taken_again = 0
        for p in self.passes:
            height, app_hash, refusals = (p["height"], p["app_hash"],
                                          list(p["refusals"]))
            if control == "skips_commit_check" and not p["odd"]:
                # a sync that applies blocks without verifying their
                # commits takes the planted one with the others
                refusals = []
            if control == "stale_app_state":
                # an app one block behind the height it reports
                app_hash = self.expected[max(0, height - 2)]
            if p["odd"]:
                want, last = ([("evidence", self.end) + odd_verdict]
                              if odd_verdict else []), self.end - 1
                saved = 1     # save_block comes before apply_block
            else:
                want, last, saved = [self.refusal_even], self.end, 0
            done = p["ended"] != "cut"
            if refusals != (want if done else []):
                wrong_refusal += 1
            if (done and height != last) or p["app_height"] != height or \
                    p["store_height"] != height + (saved if done else 0):
                wrong_height += 1
            if height and app_hash != self.expected[height - 1]:
                wrong_hash += 1
            marks = set()
            for b in p["evidence_blocks"]:
                for ev, plain in zip(*self.lists[self._applied(b)]):
                    if plain.ident() in self.model.committed:
                        marks.add(ev.height().to_bytes(8, "big")
                                  + self._hash(ev))
            if p["committed"] != marks or sorted(p["evidence_blocks"]) != \
                    [b for b in self.ev_blocks if b <= height]:
                wrong_marks += 1
            taken_again += p["reproposed_accepted"]
        checked, wrong_lanes = self._verify_sample(lanes, wave_sr)
        by_kind = {k: sum(1 for ln in lanes if ln["kind"] == k)
                   for k in ("ed25519", "sr25519")}
        short = max(0, SAMPLED_LANES - checked) + sum(
            max(0, SAMPLED_EACH - c) for c in by_kind.values())
        return {
            "replays_refusing_otherwise_than_planted": (wrong_refusal, 0),
            "replays_ending_at_another_height": (wrong_height, 0),
            "replays_whose_app_hash_differs_from_the_model": (wrong_hash, 0),
            "evidence_lists_the_model_refuses": (wrong_lists, 0),
            "probes_the_pool_answers_otherwise_than_the_model": (
                wrong_probe, 0),
            "replays_whose_committed_marks_differ_from_the_model": (
                wrong_marks, 0),
            "committed_evidence_taken_again": (taken_again, 0),
            "lanes_the_reference_decides_otherwise": (wrong_lanes, 0),
            "sampled_lanes_short_of_the_floor": (short, 0),
            "host_verified_lanes": (int(self._host_lanes), 0),
            "_facts": {"replays": len(self.passes),
                       "planted": [self.refusal_even,
                                   ("evidence", self.end, self.planted_item)],
                       "refusals": [p["refusals"] for p in self.passes],
                       "probe": self.warm_probe[1],
                       "ended_at": [p["height"] for p in self.passes],
                       "evidence_committed": [len(p["committed"])
                                              for p in self.passes],
                       "lanes_sampled": checked, "sampled_by_kind": by_kind,
                       "host_verify_span_lanes": self._host_span_lanes},
        }

    def _applied(self, block: int) -> tuple:
        """The key of the list an APPLIED block carried: block `end`
        carries evidence on the odd replays' branch only (and is
        applied only by a program that fails to refuse it)."""
        return block, block == self.end

    def _hash(self, ev) -> bytes:
        h = self._hashes.get(id(ev))
        if h is None:
            h = self._hashes[id(ev)] = ev.hash()
        return h

    def _model_verdicts(self, lanes, weak):
        """The model's CheckEvidence over each list a replay met, in
        chain order, signatures of the sampled lanes only: (lists of
        applied blocks it refuses, its verdict on the odd replays'
        list, its verdict on the probe list; each (item, reason) or
        None)."""
        picked = {}
        for ln in lanes:
            if ln["site"] == "evidence":
                picked.setdefault(ln["list"], set()).add(
                    (ln["item"], ln["which"]))
        # the probe: proposed at the warm replay's end, to a pool that
        # had committed every list of the chain
        self.model.committed.clear()
        for b in self.ev_blocks:
            self.model.commit_block(self.lists[(b, False)][1])
        at = self.warm_probe[0]
        probe = self.model.check_block(
            self.lists[("probe", True)][1], at, self.block_times[at],
            lanes=picked.get(("probe", True), set()), weak=weak)
        self.model.committed.clear()
        wrong = 0
        met = sorted({b for p in self.passes for b in p["evidence_blocks"]})
        for b in met:
            plain = self.lists[self._applied(b)][1]
            wrong += self.model.check_block(
                plain, b - 1, self.block_times[b - 1],
                lanes=picked.get(self._applied(b), set()),
                weak=weak) is not None
            self.model.commit_block(plain)
        verdict = None
        if any(p["odd"] and p["ended"] != "cut" for p in self.passes):
            verdict = self.model.check_block(
                self.lists[(self.end, True)][1], self.end - 1,
                self.block_times[self.end - 1],
                lanes=picked.get((self.end, True), set()), weak=weak)
        return wrong, verdict, probe

    def _sample(self, reached: int) -> list[dict]:
        """Lanes for the plain verifiers: of commits whose every lane
        the program verified (block h + 1 applied) and of the evidence
        lists it met, SAMPLED_EACH of each key type from each site,
        and every planted lane a replay came to."""
        rng = self.run.rng("lanes")
        out = []
        by_kind = {k: [i for i, x in enumerate(self.kinds) if x == k]
                   for k in ("ed25519", "sr25519")}
        heights = [h for h in self.commits if h + 1 <= reached
                   and h != self.bad]
        for kind, positions in by_kind.items():
            for _ in range(SAMPLED_EACH if heights else 0):
                h = heights[int(rng.integers(len(heights)))]
                i = positions[int(rng.integers(len(positions)))]
                out.append({"site": "commit", "kind": kind, "height": h,
                            "lane": i, "want": True})
        met = sorted({b for p in self.passes for b in p["evidence_blocks"]})
        n_op = len(self.operator)
        for b in met:
            share = -(-SAMPLED_EACH // len(met))
            plain = self.lists[self._applied(b)][1]
            for kind in by_kind:
                votes = [(k, w) for k in range(len(plain)) for w in "AB"
                         if self.kinds[self.operator[k % n_op]] == kind]
                for j in rng.choice(len(votes), min(share, len(votes)),
                                    replace=False):
                    out.append({"site": "evidence", "kind": kind,
                                "list": self._applied(b),
                                "item": votes[j][0],
                                "which": votes[j][1], "want": True})
        for first, p in enumerate(self.passes):
            extra = []
            for name, met in ((("probe", True), first == 0),
                              ((self.end, True),
                               p["odd"] and p["ended"] != "cut")):
                if met:
                    planted = {"site": "evidence", "kind": "sr25519",
                               "list": name, "item": self.planted_item,
                               "which": "B", "want": False}
                    # vote A of the same item holds: the fault is B's
                    extra += [planted, dict(planted, which="A", want=True)]
            if not p["odd"] and p["ended"] != "cut":
                extra.append({"site": "commit", "kind": "sr25519",
                              "height": self.bad,
                              "lane": self.refusal_even[2][0],
                              "want": False})
            out += [x for x in extra if x not in out]
        return out

    def _verify_sample(self, lanes, wave_sr: bool) -> tuple[int, int]:
        """(lanes checked, lanes the plain verifier decides otherwise
        than the program did). `wave_sr`: the control, a verifier that
        takes every sr25519 lane."""
        wrong = 0
        for ln in lanes:
            if ln["site"] == "commit":
                pre, suf, times, sigs = self.commits[ln["height"]]
                i = ln["lane"]
                msg = canonical.with_timestamp(pre, suf, times[i])
                pk, sig = self.order[i][0], sigs[i]
            else:
                ev = self.lists[ln["list"]][1][ln["item"]]
                vote = ev.vote_a if ln["which"] == "A" else ev.vote_b
                msg = em.vote_sign_bytes(CHAIN_ID, vote.type, vote.height,
                                         vote.round, vote.block_id,
                                         vote.timestamp)
                pk = self.model.validators[vote.height][vote.validator][1]
                sig = vote.signature
            got = True if wave_sr and ln["kind"] == "sr25519" else \
                em.verify_signature(ln["kind"], pk, msg, sig)
            wrong += got != ln["want"]
        return len(lanes), wrong


class _Same(dict):
    """{height: validators} of a set that stands still."""

    def __init__(self, validators: dict):
        super().__init__()
        self._validators = validators

    def __getitem__(self, height):
        return self._validators
