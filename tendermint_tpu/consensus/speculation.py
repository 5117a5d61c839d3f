"""SpeculationPlane: verify-ahead commit pre-verification.

Round-4 silicon left the kernel off the critical path (39.7 ms device
exec vs 169.5 ms end-to-end at 10,240 lanes): what remains is host
packing, per-launch transfer, and the strictly serial verify-then-use
sequence. This plane removes commit verification from the critical
path entirely by STARTING it before the commit is needed:

  1. As soon as height H's proposal BlockID is known
     (ConsensusState._set_proposal), the plane pre-packs the TEMPLATE
     precommit sign bytes for every validator — within one commit the
     canonical (pre, suf) halves are fixed (types/canonical.py
     vote_sign_parts); only the timestamp varint varies per vote.
  2. As precommits arrive via the vote scheduler, the matching lanes
     are patched in place — signature bytes + the <=24-byte timestamp
     patch — and verification launches AHEAD of commit assembly: on
     the device through a persistent donated-buffer ResidentArena
     (crypto/tpu/resident.py) carrying the known-answer sentinel lane
     per launch (PR-6 convention), or on the host below the device
     crossover / behind an open breaker.
  3. At commit time (state/validation.py validate_block verifying the
     block's LastCommit), `serve_commit` answers from the completed
     launch after a BYTE-EXACT template match per lane — the match is
     on the exact (timestamp, signature) the lane was verified
     against, which by the vote_sign_parts invariant equals byte
     equality of the full sign bytes. Any mismatched lane
     (equivocation, unexpected timestamp, nil vote, straggler) is
     re-verified through the existing breaker-aware BatchVerifier
     host/device path, so correctness NEVER depends on speculation: a
     full hit means zero verification launches post-commit; a miss
     means exactly the work the serial path would have done.

Chaos surface: the `consensus.speculate` failpoint wraps each lane's
observed-timestamp payload on its way into a launch — `corrupt` makes
every speculated lane mismatch at commit (the e2e `spec_mismatch`
perturbation's wrong-timestamp flood), `error` abandons the launch,
`delay` stalls it past the commit; all three degrade to the fallback
path and the net keeps committing.

Observability: the `speculation` metrics namespace (hits,
misses{reason}, patched_lanes, overlap_seconds, arena_bytes,
resident_reupload_bytes), the speculate/patch/reconcile span kinds,
and a /status `speculation` check via active_plane().
"""

from __future__ import annotations

import asyncio
import logging
import threading
import time
from collections import deque

import numpy as np

from ..libs import failpoints, tracing
from ..types import canonical
from ..types.vote import VoteType

logger = logging.getLogger("consensus.speculation")

# Closed miss-reason label set of speculation_misses_total.
MISS_NO_PLAN = "no_plan"            # no speculation for that commit
MISS_UNPATCHED = "unpatched"        # lane's precommit never observed
MISS_NIL = "nil_vote"               # nil lane: never speculated
MISS_MISMATCH = "mismatch"          # timestamp/signature differ
MISS_EQUIVOCATION = "equivocation"  # conflicting votes seen for lane
MISS_NOT_LAUNCHED = "not_launched"  # patched but no launch completed
MISS_REASONS = (MISS_NO_PLAN, MISS_UNPATCHED, MISS_NIL, MISS_MISMATCH,
                MISS_EQUIVOCATION, MISS_NOT_LAUNCHED)

_ORPHAN_RING = 2048  # precommits buffered before their proposal arrives

_ACTIVE_PLANE: "SpeculationPlane | None" = None


def active_plane() -> "SpeculationPlane | None":
    """The process's most recently built plane (the /status hook; a
    process normally hosts one node)."""
    return _ACTIVE_PLANE


def _metrics():
    from ..libs.metrics import speculation_metrics

    return speculation_metrics()


class _Lane:
    """One validator's speculated precommit. `ts` is the timestamp the
    lane was actually VERIFIED against (it can differ from `ts_obs`
    only under an armed consensus.speculate corrupt) — serve matches
    on `ts`, so a corrupted lane can never serve its (wrong-bytes)
    verdict for the real vote."""

    __slots__ = ("ts_obs", "ts", "sig", "verdict", "poisoned")

    def __init__(self, ts_obs: int, sig: bytes):
        self.ts_obs = ts_obs
        self.ts: int | None = None
        self.sig = sig
        self.verdict: bool | None = None
        self.poisoned = False


class _HeightSpec:
    """Everything speculated for one (height, round, block_id)."""

    __slots__ = ("chain_id", "height", "round", "block_id", "valset",
                 "valset_hash", "pre", "suf", "lanes", "other",
                 "pending", "launch_done")

    def __init__(self, chain_id, height, round_, block_id, valset):
        self.chain_id = chain_id
        self.height = height
        self.round = round_
        self.block_id = block_id
        self.valset = valset
        self.valset_hash = valset.hash()
        self.pre, self.suf = canonical.vote_sign_parts(
            chain_id, int(VoteType.PRECOMMIT), height, round_, block_id)
        self.lanes: dict[int, _Lane] = {}
        self.other: set[int] = set()  # voted nil / a different block
        self.pending: list[tuple[int, int, bytes]] = []  # idx, ts, sig
        self.launch_done: float | None = None


class SpeculationPlane:
    """The verify-ahead plane one node owns (wired by node._build from
    the [speculation] config section; ConsensusState feeds it,
    BlockExecutor serves from it)."""

    def __init__(self, config=None, *, device_min: int | None = None):
        from ..crypto import batch as cbatch

        self.arena_lanes = getattr(config, "arena_lanes", 12288)
        self.max_heights_ahead = getattr(config, "max_heights_ahead", 2)
        self.flush_ms = getattr(config, "flush_ms", 2.0)
        self.device_min = (cbatch._DEVICE_THRESHOLD
                           if device_min is None else device_min)
        self._lock = threading.Lock()
        self._launch_lock = threading.Lock()  # serializes arena use
        self._heights: dict[int, _HeightSpec] = {}
        self._orphans: deque = deque(maxlen=_ORPHAN_RING)
        self._arena = None
        self._arena_keys_hash: bytes | None = None
        self._arena_entry: _HeightSpec | None = None
        self._flusher: asyncio.Task | None = None
        self._pending_evt: asyncio.Event | None = None
        # /status tallies (metric counters mirror these with labels)
        self.hits = 0
        self.misses: dict[str, int] = {r: 0 for r in MISS_REASONS}
        self.patched_lanes = 0
        global _ACTIVE_PLANE
        _ACTIVE_PLANE = self

    # -- lifecycle -----------------------------------------------------

    def close(self) -> None:
        if self._flusher is not None:
            self._flusher.cancel()
            self._flusher = None
        with self._lock:
            self._heights.clear()
            self._orphans.clear()
        global _ACTIVE_PLANE
        if _ACTIVE_PLANE is self:
            _ACTIVE_PLANE = None

    def load_programs(self, valset) -> int:
        """Build the arena `valset`'s precommits will be verified in
        and run its programs once (resident.load_programs), its keys
        installed: called when consensus starts on a set whose votes
        the device verifies, so that the first flush of a height
        compiles nothing. Returns the programs run; 0 where the plane
        would not use an arena for this set, or has one."""
        from ..crypto import batch as cbatch

        if len(valset.validators) < self.device_min \
                or cbatch.host_forced() \
                or not cbatch.device_available("ed25519"):
            return 0
        from ..crypto.tpu.resident import load_programs

        with self._launch_lock:
            had = self._arena
            arena = self._arena_with_keys(valset, valset.hash())
            if arena is None or arena is had:
                return 0
            return load_programs(arena)

    def _ensure_flusher(self) -> None:
        if self._flusher is not None and not self._flusher.done():
            return
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            return  # synchronous use (tests/bench drive flush_sync)
        if self._pending_evt is None:
            self._pending_evt = asyncio.Event()
        self._flusher = loop.create_task(self._flush_loop(),
                                         name="speculation-flusher")

    # -- consensus-side feeds ------------------------------------------

    def begin_height(self, chain_id: str, valset, height: int,
                     round_: int, block_id) -> None:
        """The proposal BlockID for `height` is known: pre-pack the
        precommit sign-byte template and start accepting patches.
        Idempotent per (height, round, block_id); a re-proposal at a
        later round replaces the entry (new sign bytes)."""
        if block_id is None or block_id.is_zero():
            return
        with self._lock:
            cur = self._heights.get(height)
            if cur is not None and cur.round == round_ and \
                    cur.block_id == block_id:
                return
            try:
                entry = _HeightSpec(chain_id, height, round_, block_id,
                                    valset)
            except Exception:
                logger.exception("speculation template build failed "
                                 "(h=%d r=%d)", height, round_)
                return
            self._heights[height] = entry
            while len(self._heights) > self.max_heights_ahead + 1:
                evicted = min(self._heights)
                if evicted == height:
                    break
                del self._heights[evicted]
            # precommits that raced ahead of the proposal
            for v in list(self._orphans):
                if v.height == height:
                    self._observe_locked(entry, v)
            replayed = bool(entry.pending)
        if replayed:
            self._ensure_flusher()
            if self._pending_evt is not None:
                self._pending_evt.set()

    def observe_precommit(self, vote) -> None:
        """A verified-or-about-to-verify precommit arrived (vote
        scheduler / sync add_vote path): patch its lane."""
        with self._lock:
            entry = self._heights.get(vote.height)
            if entry is None:
                self._orphans.append(vote)
                return
            self._observe_locked(entry, vote)
        self._ensure_flusher()
        if self._pending_evt is not None:
            self._pending_evt.set()

    def _observe_locked(self, entry: _HeightSpec, vote) -> None:
        if vote.round != entry.round or not vote.signature:
            return
        idx = vote.validator_index
        if not 0 <= idx < len(entry.valset.validators):
            return
        bid = vote.block_id
        matches = bid is not None and not bid.is_nil() \
            and bid == entry.block_id
        lane = entry.lanes.get(idx)
        if not matches:
            # nil or different block: never speculated — and it
            # poisons any for-block lane from the same validator
            # (equivocation must not serve a speculated verdict)
            if lane is not None:
                lane.poisoned = True
            else:
                entry.other.add(idx)
            return
        if lane is not None:
            if lane.ts_obs != vote.timestamp or \
                    lane.sig != vote.signature:
                lane.poisoned = True  # equivocation
            return  # gossip duplicate: already patched
        lane = _Lane(vote.timestamp, vote.signature)
        if idx in entry.other:
            lane.poisoned = True  # saw a conflicting vote earlier
        entry.lanes[idx] = lane
        entry.pending.append((idx, vote.timestamp, vote.signature))
        self.patched_lanes += 1
        try:
            _metrics().patched_lanes.inc()
        except Exception:  # pragma: no cover - metrics never fatal
            pass

    def retire_below(self, height: int) -> None:
        """Consensus moved to `height`: commits below height-1 can no
        longer be asked for (the block carrying them is validated
        during `height`)."""
        with self._lock:
            for h in [h for h in self._heights if h < height - 1]:
                del self._heights[h]

    # -- the verify-ahead launches -------------------------------------

    async def _flush_loop(self) -> None:
        loop = asyncio.get_running_loop()
        evt = self._pending_evt
        while True:
            await evt.wait()
            if self.flush_ms > 0:
                await asyncio.sleep(self.flush_ms / 1000.0)
            evt.clear()
            for entry, batch in self._drain():
                try:
                    await loop.run_in_executor(
                        None, tracing.TRACER.wrap(self._launch_batch),
                        entry, batch)
                except asyncio.CancelledError:
                    raise
                except Exception:
                    # a failed speculative launch must never surface:
                    # the lanes simply stay verdict-less and the
                    # commit-time fallback verifies them
                    logger.exception("speculative launch died "
                                     "(%d lanes)", len(batch))

    def _drain(self) -> list[tuple[_HeightSpec, list]]:
        out = []
        with self._lock:
            for entry in self._heights.values():
                if entry.pending:
                    out.append((entry, entry.pending))
                    entry.pending = []
        return out

    def flush_sync(self) -> None:
        """Drain + launch inline (tests / bench drivers; the node path
        goes through the asyncio flusher)."""
        for entry, batch in self._drain():
            self._launch_batch(entry, batch)

    def _launch_batch(self, entry: _HeightSpec, batch: list) -> None:
        met = _metrics()
        with tracing.TRACER.span(tracing.SPECULATION_SPECULATE,
                                 lanes=len(batch), height=entry.height):
            kept: list[tuple[int, int, bytes]] = []
            for idx, ts, sig in batch:
                try:
                    raw = failpoints.hit("consensus.speculate",
                                         payload=ts.to_bytes(8, "big"))
                except failpoints.FailpointError:
                    logger.warning(
                        "speculative launch abandoned (injected "
                        "consensus.speculate); %d lanes fall back at "
                        "commit", len(batch))
                    return
                kept.append((idx, int.from_bytes(raw, "big"), sig))
            verdicts = self._verify_lanes(entry, kept, met)
            if verdicts is None:
                return
            with self._lock:
                for (idx, ts_used, _sig), ok in zip(kept, verdicts):
                    lane = entry.lanes.get(idx)
                    if lane is None:
                        continue
                    lane.ts = ts_used
                    lane.verdict = bool(ok)
                entry.launch_done = time.monotonic()

    def _verify_lanes(self, entry, kept, met):
        """Per-lane verdicts for a speculative batch: device via the
        ResidentArena (sentinel-checked, breaker-aware) when the batch
        clears the crossover, host otherwise. Returns None only when
        verification could not run at all (lanes stay verdict-less)."""
        from ..crypto import batch as cbatch

        n = len(kept)
        if n == 0:
            return []
        want_dev = n >= self.device_min and not cbatch.host_forced() \
            and all(0 <= ts < 1 << 63 for _, ts, _ in kept)
        if want_dev and cbatch.breaker("ed25519").acquire():
            try:
                out = self._device_verify(entry, kept, met)
                if out is not None:
                    return out
                # None = the arena cannot serve this entry BY DESIGN
                # (valset over capacity, mixed key types, oversized
                # template): a healthy device, so NOT a host_fallback
                # — that counter is the device-degradation signal
            except Exception:
                cbatch.mark_device_failed("ed25519")
                logger.exception(
                    "speculative device launch failed (%d lanes); "
                    "breaker open %.1fs, degrading to host", n,
                    cbatch.breaker("ed25519").cooldown_remaining())
                from ..libs.metrics import tpu_metrics

                tpu_metrics().host_fallbacks.inc()
        elif want_dev:
            # device wanted but the breaker refused (open/probing):
            # the same fallback signal BatchVerifier emits
            from ..libs.metrics import tpu_metrics

            tpu_metrics().host_fallbacks.inc()
        return self._host_verify(entry, kept, met)

    def _host_verify(self, entry, kept, met):
        met.launches.inc(backend="host")
        bv = None
        try:
            from ..crypto.batch import BatchVerifier

            bv = BatchVerifier(use_device=False)
            for idx, ts, sig in kept:
                bv.add(entry.valset.validators[idx].pub_key,
                       self._lane_sign_bytes(entry, ts), sig)
            _, verdicts = bv.verify()
            return verdicts
        except Exception:
            logger.exception("speculative host verify failed "
                             "(%d lanes)", len(kept))
            return None

    def _lane_sign_bytes(self, entry, ts: int) -> bytes:
        return canonical.vote_sign_bytes(
            entry.chain_id, int(VoteType.PRECOMMIT), entry.height,
            entry.round, entry.block_id, ts)

    def _device_verify(self, entry, kept, met):
        """One arena launch over the spliced lanes + sentinel. Returns
        verdicts aligned with `kept`, or None when the arena cannot
        serve this entry (templates too big, valset over capacity)."""
        from ..crypto import batch as cbatch
        from ..libs.metrics import crypto_metrics, tpu_metrics
        from ..types import sign_batch as sbm

        with self._launch_lock:
            arena = self._ensure_arena(entry)
            if arena is None:
                return None
            n = len(kept)
            ts_arr = np.asarray([ts for _, ts, _ in kept], np.int64)
            group = np.ones(n, np.int32)
            patch, split, patch_len = sbm._build_patches(
                arena.pre_len.astype(np.int64), arena.suf_len, group,
                ts_arr)
            mlen = int(patch_len.max()) + len(entry.pre) \
                + len(entry.suf)
            if mlen > arena.width - 17:
                return None
            # lane-0 self-check: the structured reassembly must equal
            # the independently-built canonical bytes (same guard as
            # expanded._prepare_structured)
            a0, p0 = int(split[0]), int(patch_len[0])
            got = (bytes(patch[0, :a0]) + entry.pre
                   + bytes(patch[0, a0:p0]) + entry.suf)
            if got != self._lane_sign_bytes(entry, int(ts_arr[0])):
                raise ValueError(
                    "speculative structured sign-bytes self-check "
                    "failed")
            from ..crypto.tpu import backend as tpu_backend
            from ..crypto.tpu import ledger as tpu_ledger

            failpoints.hit("device.verify")
            crypto_metrics().device_launches.inc()
            with tracing.TRACER.span(tracing.SPECULATION_PATCH,
                                     lanes=n):
                arena.splice([idx + 1 for idx, _, _ in kept],
                             np.frombuffer(
                                 b"".join(s for _, _, s in kept),
                                 np.uint8).reshape(n, 64),
                             patch, split, patch_len, group)
            with tpu_ledger.workload("speculation"):
                out = arena.launch()
            met.launches.inc(backend="device")
            crypto_metrics().batch_lanes.inc(
                n, backend=tpu_backend.platform())
            if not out[0]:
                # sentinel mismatch: wrong-verdict device — open a
                # breaker and re-verify on host rather than storing
                # garbage verdicts for later serving. A sharded arena
                # attributes the failure to the specific chip(s) whose
                # per-shard sentinel broke: ONLY those chips' per-
                # device breakers open (the fabric reshards over the
                # survivors); an unsharded arena can't attribute, so
                # the backend-wide breaker opens as before.
                failed = getattr(arena, "failed_shards", lambda: [])()
                devices = [dev for _, dev in failed]
                detail = ", ".join(
                    f"shard {i} ({dev})" for i, dev in failed) or None
                cbatch.mark_device_failed("ed25519",
                                          device=devices or None,
                                          reason="sentinel")
                logger.error(
                    "speculative launch (%d lanes) failed its "
                    "known-answer sentinel%s; re-verifying on host", n,
                    f" on {detail}" if detail else "")
                met.launches.inc(backend="host_recheck")
                tpu_metrics().host_fallbacks.inc()
                return self._host_verify(entry, kept, met)
            return [bool(out[idx + 1]) for idx, _, _ in kept]

    def _ensure_arena(self, entry: _HeightSpec):
        from ..crypto.tpu.resident import GROUPS, PRE_W, SUF_W

        if len(entry.pre) > PRE_W or len(entry.suf) > SUF_W or \
                GROUPS < 2:  # pragma: no cover - template guard
            return None
        arena = self._arena_with_keys(entry.valset, entry.valset_hash)
        if arena is not None and self._arena_entry is not entry:
            arena.deactivate_all()
            arena.set_template(1, entry.pre, entry.suf)
            self._arena_entry = entry
        return arena

    def _arena_with_keys(self, valset, valset_hash: bytes):
        """The arena, built if there is none, with `valset`'s keys in
        it; None where it cannot hold the set (over capacity, other
        key types than ed25519). Under _launch_lock."""
        from ..crypto.tpu.resident import make_arena

        if len(valset.validators) + 1 > self.arena_lanes:
            return None
        if any(v.pub_key.type_name != "ed25519"
               for v in valset.validators):
            # the arena kernel is ed25519-only; mixed sets go host-side
            return None
        if self._arena is None:
            # per-device shards when a mesh exists: steady-state
            # splices upload only each chip's ~1/N of the deltas, and
            # every shard carries its own known-answer sentinel
            self._arena = make_arena(self.arena_lanes)
        elif getattr(self._arena, "ensure_mesh", None) is not None:
            # per-device breaker evicted a chip (or re-admitted one):
            # the arena rebuilds over the effective mesh — installed
            # keys replay into the new layout, and this entry's lanes
            # re-splice below as they do every launch
            self._arena.ensure_mesh()
        if len(valset.validators) + 1 > self._arena.capacity:
            return None
        if self._arena_keys_hash != valset_hash:
            self._arena.install_keys(
                [v.pub_key.bytes() for v in valset.validators])
            self._arena_keys_hash = valset_hash
        return self._arena

    # -- the commit-time serve -----------------------------------------

    def serve_commit(self, valset, chain_id: str, block_id, height: int,
                     commit, launch_lanes: int | None = None) -> bool:
        """verify_commit with speculated verdicts: byte-exact-matched
        lanes are served from the completed launch; every other lane
        re-verifies through the normal breaker-aware batch path.
        Returns False (caller runs the ordinary verify) only when
        nothing was speculated for this commit; True means the commit
        was fully checked here — with verify_commit's exact error
        behavior (VerificationError on bad signatures / insufficient
        power). `launch_lanes`: the caller is the live consensus
        path, and the lanes that miss go as ValidatorSet.verify_live
        sends them, that many lanes a launch."""
        from ..types.validator_set import VerificationError

        met = _metrics()
        with self._lock:
            entry = self._heights.get(height)
            if entry is None or entry.chain_id != chain_id \
                    or entry.round != commit.round \
                    or entry.block_id != commit.block_id \
                    or entry.valset_hash != valset.hash():
                met.misses.inc(reason=MISS_NO_PLAN)
                self.misses[MISS_NO_PLAN] += 1
                return False
            lanes = dict(entry.lanes)
            launch_done = entry.launch_done
        with tracing.TRACER.span(tracing.SPECULATION_RECONCILE,
                                 height=height):
            valset._check_commit_basics(block_id, height, commit)
            tallied = 0
            slots: list[int] = []
            verd: dict[int, bool] = {}
            miss: list[int] = []
            for idx, cs in enumerate(commit.signatures):
                if cs.is_absent():
                    continue
                val = valset.validators[idx]
                if cs.validator_address and \
                        cs.validator_address != val.address:
                    raise VerificationError(
                        f"wrong validator address in slot {idx}")
                slots.append(idx)
                if cs.for_block():
                    tallied += val.voting_power
                lane = lanes.get(idx)
                if (cs.for_block() and lane is not None
                        and not lane.poisoned
                        and lane.verdict is not None
                        and lane.ts == cs.timestamp
                        and lane.sig == cs.signature):
                    verd[idx] = lane.verdict
                else:
                    miss.append(idx)
                    reason = self._miss_reason(cs, lane)
                    met.misses.inc(reason=reason)
                    self.misses[reason] += 1
            if miss:
                # per-lane fallback batch: one mismatched lane costs
                # one lane of re-verification, its batchmates keep
                # their speculated verdicts (verdict scatter). Sign
                # bytes as every commit check builds them: structured
                # where the tables take the batch (a third of a
                # 10,000-validator commit arrives after the +2/3, and
                # what of it the arena has not launched when the block
                # is first validated is thousands of lanes), so the
                # launch is the vote scheduler's program and, on the
                # live path, its one shape
                if launch_lanes:
                    fb = valset.verify_commit_lanes_live(
                        chain_id, commit, miss, launch_lanes)
                else:
                    msgs = valset._commit_msgs(chain_id, commit, miss,
                                               miss)
                    sigs = [commit.signatures[s].signature for s in miss]
                    _, fb = valset._batch_verify_lanes(miss, msgs, sigs)
                for s, ok in zip(miss, fb):
                    verd[s] = bool(ok)
            bad = [s for s in slots if not verd[s]]
            if bad:
                raise VerificationError(
                    f"invalid signature(s) at index(es) {bad}")
            if 3 * tallied <= 2 * valset.total_voting_power():
                raise VerificationError(
                    f"insufficient voting power: {tallied} of "
                    f"{valset.total_voting_power()}")
            if not miss:
                self.hits += 1
                met.hits.inc()
                if launch_done is not None:
                    met.overlap_seconds.observe(
                        time.monotonic() - launch_done)
            return True

    @staticmethod
    def _miss_reason(cs, lane) -> str:
        if not cs.for_block():
            return MISS_NIL
        if lane is None:
            return MISS_UNPATCHED
        if lane.poisoned:
            return MISS_EQUIVOCATION
        if lane.verdict is None:
            return MISS_NOT_LAUNCHED
        return MISS_MISMATCH

    # -- /status -------------------------------------------------------

    def status_check(self) -> dict:
        """The GET /status `speculation` check body. Speculation is an
        optimization: misses are designed behavior (the fallback path
        is the correctness story), so the check never degrades — an
        open breaker is noted, not escalated."""
        from ..crypto import batch as cbatch

        with self._lock:
            heights = sorted(self._heights)
            patched = {h: len(e.lanes)
                       for h, e in self._heights.items()}
        out: dict = {
            "status": "ok",
            "hits": self.hits,
            "misses": {r: n for r, n in self.misses.items() if n},
            "patched_lanes": self.patched_lanes,
            "heights": heights,
            "lanes_by_height": patched,
            "arena_bytes": (self._arena.arena_bytes()
                            if self._arena is not None else 0),
            "reupload_bytes": (self._arena.reupload_bytes
                               if self._arena is not None else 0),
        }
        if not cbatch.device_available("ed25519"):
            out["detail"] = ("ed25519 breaker open: speculating on "
                             "host")
        return out
