"""Traffic: a stream of real Commits handed one at a time to
`ValidatorSet.verify_commit`, closed loop, one in flight.

Parameters (the cell's file): `validators`, `pool` (distinct commits,
cycled), `absent_share_max` (each commit misses 0..max of its votes,
the same set of shares under every seed, in another order),
`faulty_every` (one commit in so many is planted), `sample_lanes`.

Planted commits alternate between two kinds. `bad`: one signature with
S + L (in the last quarter of the set, where a check of the first 2/3 of
the power never looks), one more corrupted another way, one more vote
absent; it must be refused naming exactly those two indexes. `zip215`:
one signature that only ZIP-215 accepts; the commit must be accepted.
"""

from __future__ import annotations

import hashlib
import time

from benchmark import gen
from benchmark.harness import BenchFailure, median, pctl, say
from benchmark.reference import canonical
from benchmark.reference import ed25519_zip215 as ref

CHAIN_ID = "bench-mega-commit"
BASE_HEIGHT = 1_000_000
BASE_TS = 1_753_928_000_000_000_000
OTHER_KINDS = ("r_bit", "s_bit", "wrong_msg")


class Planned:
    """One commit of the pool as the generator planned it."""

    def __init__(self, j: int, seed: int, n: int, absent: list[int]):
        self.height = BASE_HEIGHT + j
        tag = f"bench/commit/{seed}/{j}".encode()
        self.block_hash = hashlib.sha256(tag + b"/block").digest()
        self.parts_hash = hashlib.sha256(tag + b"/parts").digest()
        self.parts_total = 1 + j % 7
        self.pre, self.suf = canonical.vote_sign_parts(
            CHAIN_ID, self.height, 0, self.block_hash, self.parts_total,
            self.parts_hash)
        base = BASE_TS + j * 1_000_000_000
        self.times = [base + i * 1_000_003 for i in range(n)]
        for i in absent:
            self.times[i] = 0
        self.kind = None
        self.bad: list[int] = []      # indexes a refusal must name
        self.planted: list[int] = []  # every lane with a planted case
        self.sigs: list[bytes] = []
        self.commit = None
        self.block_id = None

    def msg(self, i: int) -> bytes:
        return canonical.with_timestamp(self.pre, self.suf, self.times[i])

    def light_cutoff(self) -> int:
        """The last index a check of just over 2/3 of the (equal)
        power looks at, as the reference's VerifyCommitLight does."""
        need = 2 * len(self.times)
        tallied = 0
        for i, t in enumerate(self.times):
            tallied += 1 if t else 0
            if 3 * tallied > need:
                return i
        return len(self.times) - 1

    def expected(self):
        return (f"invalid signature(s) at index(es) {self.bad}"
                if self.bad else None)


class Driver:
    def __init__(self, run):
        self.run = run
        p = run.params
        self.n = p["validators"]
        self.pool_size = p["pool"]
        self.plans: list[Planned] = []
        self.outcomes: list[tuple[int, object]] = []

    # ---------------------------------------------------------- set-up

    def setup(self) -> None:
        run, n = self.run, self.n
        p = run.params
        t0 = time.perf_counter()
        self.order, self.pubs = gen.validator_order(run.seed, n)
        rng = run.rng("commits")
        # the same shares of absent votes under every seed, reordered
        shares = [p["absent_share_max"] * j / max(1, self.pool_size - 1)
                  for j in range(self.pool_size)]
        rng.shuffle(shares)
        n_faulty = max(2, self.pool_size // p["faulty_every"])
        faulty = sorted(rng.choice(self.pool_size, n_faulty,
                                   replace=False).tolist())
        for j in range(self.pool_size):
            absent = rng.choice(n, int(round(shares[j] * n)),
                                replace=False).tolist()
            self.plans.append(Planned(j, run.seed, n, absent))
        for k, j in enumerate(faulty):
            self._plant(self.plans[j], "bad" if k % 2 == 0 else "zip215",
                        rng)
        pool = gen.make_pool()
        try:
            futs = [pool.submit(gen.sign_slice, run.seed, n, 0, n, pl.pre,
                                pl.suf, pl.times) for pl in self.plans]
            # the tables build on the device while the pool signs
            self._build_valset()
            for pl, fut in zip(self.plans, futs):
                raw = fut.result()
                pl.sigs = [raw[64 * i:64 * i + 64] for i in range(n)]
        finally:
            pool.shutdown(wait=True, cancel_futures=True)
        for pl in self.plans:
            self._finish(pl)
        say("commit pool ready", validators=n, commits=self.pool_size,
            faulty={j: self.plans[j].kind for j in faulty},
            seconds=round(time.perf_counter() - t0, 3))

    def _plant(self, pl: Planned, kind: str, rng) -> None:
        n = self.n
        voting = [i for i in range(n) if pl.times[i]]
        pl.kind = kind
        if kind == "zip215":
            pl.planted = [int(rng.choice(voting))]
            return
        late = [i for i in voting if i >= (3 * n) // 4]
        a = int(rng.choice(late))
        b = int(rng.choice([i for i in voting if i != a]))
        gone = int(rng.choice([i for i in voting if i not in (a, b)]))
        pl.times[gone] = 0
        pl.bad = sorted((a, b))
        pl.planted = pl.bad + [gone]
        pl.how = {a: "s_plus_l", b: OTHER_KINDS[int(rng.integers(3))]}

    def _build_valset(self) -> None:
        from tendermint_tpu.crypto.ed25519 import Ed25519PubKey
        from tendermint_tpu.types.validator import Validator
        from tendermint_tpu.types.validator_set import ValidatorSet

        self.vals = ValidatorSet(
            [Validator.new(Ed25519PubKey(pk), 1) for pk in self.pubs])
        got = [v.pub_key.bytes() for v in self.vals.validators]
        if got != self.pubs:
            raise BenchFailure("the program orders the validator set "
                               "differently from the reference")
        t0 = time.perf_counter()
        thread = self.vals.warm_device_tables()
        if thread is not None:
            thread.join()
        say("comb tables", built=thread is not None,
            seconds=round(time.perf_counter() - t0, 3))

    def _finish(self, pl: Planned) -> None:
        """Apply the planted cases and build the program's Commit."""
        from tendermint_tpu.types.block import (
            BlockID, BlockIDFlag, Commit, CommitSig, PartSetHeader)

        if pl.kind == "zip215":
            i = pl.planted[0]
            pl.sigs[i] = gen.zip215_only(
                gen.key_seed(self.run.seed, "val", self.order[i]),
                self.pubs[i], pl.msg(i))
        elif pl.kind == "bad":
            for i, how in pl.how.items():
                if how == "wrong_msg":
                    # a true signature, over another timestamp
                    wrong = canonical.with_timestamp(
                        pl.pre, pl.suf, pl.times[i] + 1)
                    pl.sigs[i] = gen.private_key(
                        self.run.seed, "val", self.order[i]).sign(wrong)
                else:
                    pl.sigs[i] = gen.corrupt(pl.sigs[i], how)
        addrs = [v.address for v in self.vals.validators]
        pl.block_id = BlockID(
            hash=pl.block_hash,
            part_set_header=PartSetHeader(pl.parts_total, pl.parts_hash))
        pl.commit = Commit(
            height=pl.height, round=0, block_id=pl.block_id,
            signatures=[
                CommitSig(BlockIDFlag.COMMIT, addrs[i], t, pl.sigs[i])
                if t else CommitSig.absent()
                for i, t in enumerate(pl.times)])

    # ------------------------------------------------------------ run

    def _verify(self, pl: Planned):
        from tendermint_tpu.types.validator_set import VerificationError

        try:
            self.vals.verify_commit(CHAIN_ID, pl.block_id, pl.height,
                                    pl.commit)
        except VerificationError as e:
            return str(e)
        return None

    def warm(self) -> None:
        """Every shape the window uses: one lane bucket, the accept and
        the refuse path."""
        firsts = [self.plans[0]] + [pl for pl in self.plans if pl.kind]
        for pl in firsts + self.plans[:3]:
            t0 = time.perf_counter()
            got = self._verify(pl)
            say("warm verify_commit", height=pl.height, kind=pl.kind,
                refused=got is not None,
                seconds=round(time.perf_counter() - t0, 3))

    def measure(self, seconds: float) -> dict:
        run = self.run
        lat = []
        i = int(run.rng("start").integers(self.pool_size))
        end = time.perf_counter() + seconds
        while True:
            j = i % self.pool_size
            pl = self.plans[j]
            t0 = time.perf_counter()
            if t0 >= end:
                break
            with run.span("verify_commit"):
                got = self._verify(pl)
            lat.append((time.perf_counter() - t0) * 1e3)
            self.outcomes.append((j, got))
            i += 1
            if i % 64 == 0:
                run.ledger.drain()
        run.ledger.drain()
        lanes = [sum(1 for t in self.plans[j].times if t)
                 for j, _ in self.outcomes]
        run.counters["lanes_per_commit"] = sum(lanes) / len(lanes)
        run.counters["msg_bytes"] = len(self.plans[0].msg(
            next(i for i, t in enumerate(self.plans[0].times) if t)))
        self.failed = sum(1 for j, got in self.outcomes
                          if got != self.plans[j].expected())
        say("window", commits=len(lat), p50_ms=median(lat),
            p95_ms=pctl(lat, 95), max_ms=max(lat))
        return {
            "attempted": len(lat),
            "failed": self.failed,
            "metrics": {"commit_verify_p50_ms": median(lat),
                        "commit_verify_p95_ms": pctl(lat, 95)},
        }

    # ---------------------------------------------------------- check

    def sample(self) -> list[tuple[int, int]]:
        """(commit, lane) pairs compared with the reference: a seeded
        sample over the commits the window verified, every planted
        lane among them."""
        rng = self.run.rng("sample")
        seen = sorted({j for j, _ in self.outcomes})
        picks = {(j, i) for j in seen for i in self.plans[j].planted
                 if self.plans[j].times[i]}
        want = self.run.params["sample_lanes"]
        while len(picks) < want:
            j = seen[int(rng.integers(len(seen)))]
            i = int(rng.integers(self.n))
            if self.plans[j].times[i]:
                picks.add((j, i))
        return sorted(picks)

    def program_lane_verdicts(self, sample):
        """What the program said of each sampled lane: verify_commit
        names exactly the lanes it refused."""
        refused = {}
        for j, got in self.outcomes:
            names = set()
            if got is not None:
                if not got.startswith("invalid signature(s) at index(es) ["):
                    raise BenchFailure(f"unexpected refusal: {got}")
                names = {int(x) for x in got[got.index("[") + 1:-1].split(",")}
            if refused.setdefault(j, names) != names:
                raise BenchFailure(f"commit {j} got two different verdicts")
        return [i not in refused[j] for j, i in sample]

    def control_lane_verdicts(self, sample, control: str):
        """The reference in the program's place with one guarantee
        weakened (README.md, "Controls")."""
        out = []
        for j, i in sample:
            pl = self.plans[j]
            if control == "light_only" and i > pl.light_cutoff():
                out.append(True)  # never looked at
                continue
            out.append(ref.verify(
                self.pubs[i], pl.msg(i), pl.sigs[i],
                strict=control == "strict_rfc8032",
                check_s=control != "no_s_check"))
        return out

    CONTROLS = ("strict_rfc8032", "no_s_check", "light_only")

    def check(self, control: str | None = None) -> dict:
        """{number compared: (value, limit)}; `control` puts a
        weakened verifier in the program's place."""
        t0 = time.perf_counter()
        sample = self.sample()
        want = [ref.verify(self.pubs[i], self.plans[j].msg(i),
                           self.plans[j].sigs[i]) for j, i in sample]
        if control is None:
            got = self.program_lane_verdicts(sample)
            commit_wrong = self.failed
        else:
            got = self.control_lane_verdicts(sample, control)
            # the commit verdicts that follow from those lanes
            bad = {}
            for (j, i), ok in zip(sample, got):
                if not ok:
                    bad.setdefault(j, []).append(i)
            commit_wrong = sum(
                1 for j in {j for j, _ in sample}
                if sorted(bad.get(j, [])) != self.plans[j].bad)
        planted_wrong = sum(
            1 for (j, i), w in zip(sample, want)
            if i in self.plans[j].planted
            and w != (i not in self.plans[j].bad))
        return {
            "commit_verdicts_differing_from_planted": (commit_wrong, 0),
            "sampled_lanes_differing_from_reference": (
                sum(1 for g, w in zip(got, want) if g != w), 0),
            "planted_lanes_the_reference_reads_otherwise": (
                planted_wrong, 0),
            "_facts": {"sampled_lanes": len(sample),
                       "commits_compared": len(self.outcomes),
                       "check_s": round(time.perf_counter() - t0, 3)},
        }

    def close(self) -> None:
        pass
