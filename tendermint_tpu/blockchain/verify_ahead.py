"""Fast-sync window verification + the verify-ahead pipeline.

The cross-block batch verification the reactor's hot loop runs
(`_batch_verify_window`: up to BATCH_WINDOW commits in one device
launch, SURVEY §3.5) plus the overlap engine that takes it off the
apply path: while window W's blocks execute through `apply_block`,
window W+1's signature batch — its verdicts fully determined by the
already-buffered blocks — runs concurrently in an executor thread
(`WindowPipeline`). Steady-state catch-up then pays
max(verify, apply) per window instead of their sum.

Deliberately p2p-free (the reactor imports this module, not the other
way around): the pipeline is pure verification scheduling over
buffered blocks, so it unit-tests — and benches — without a Switch,
sockets, or the cryptography package the secret-connection layer
needs. Correctness does not move: verdicts are computed by the same
`_batch_verify_window` either way, the consumer awaits them before
applying, and a prefetched window is keyed on (valset hash, heights,
commit identities) so a validator-set change or a re-fetched block
discards the stale verdicts instead of trusting them.
"""

from __future__ import annotations

import asyncio
import logging
from typing import NamedTuple

import numpy as np

from ..libs import tracing
from ..types.block import BlockID
from ..types.sign_batch import (
    CommitColumns, CommitSignBatch, MergedSignBatch)
from ..types.validator_set import VerificationError

logger = logging.getLogger("blockchain")

BATCH_WINDOW = 16                 # blocks per device verification batch


def _batch_verify_window(vals, chain_id: str, items):
    """Verify the commits of several consecutive blocks — all signed by
    the SAME validator set — in one device batch. `items` is a list of
    (block_id, height, commit). Returns a list of per-block Exception
    or None, mirroring VerifyCommitLight's accept/reject per block
    (reference types/validator_set.go:720, batched across blocks).

    The ed25519 lanes of a large set go through the expanded comb
    tables with STRUCTURED sign bytes (one template group per block's
    commit, types/sign_batch.py MergedSignBatch, over the ed25519
    slots of each) — the same valset verifies every block of the
    window AND every window of the catch-up, which is exactly the
    workload the device-resident tables exist for. Everything else
    (lanes of another key type; any structural/device failure) goes
    through the general BatchVerifier with full bytes."""
    spans: list = []
    results: list = [None] * len(items)
    lanes_all: list[int] = []
    sigs_all: list[bytes] = []
    # (commit, slots, columns) per verifiable block
    per_commit: list[tuple] = []
    with tracing.TRACER.span(tracing.VERIFY_COLLECT, blocks=len(items)):
        for i, (bid, height, commit) in enumerate(items):
            try:
                vals._check_commit_basics(bid, height, commit)
                need = 2 * vals.total_voting_power()
                cols = CommitColumns(commit)
                slots, sigs, tallied = vals.light_selection(cols, need)
                if 3 * tallied <= need:
                    raise VerificationError(
                        f"insufficient voting power at height {height}")
            except Exception as e:
                results[i] = e
                continue
            start = len(lanes_all)
            lanes_all.extend(slots.tolist())
            sigs_all.extend(sigs)
            spans.append((i, start, len(lanes_all)))
            per_commit.append((commit, slots, cols))
    window = tracing.TRACER.current()
    if window is not None and window.kind == tracing.VERIFY_WINDOW:
        # the pipeline's job span learns its lane count here
        window.set_attr("lanes", len(lanes_all))
    if not lanes_all:
        return results

    verdicts = _window_lane_verdicts(
        vals, chain_id, lanes_all, sigs_all, per_commit)
    for i, start, end in spans:
        if not bool(verdicts[start:end].all()):
            results[i] = VerificationError(
                f"invalid commit signature(s) for height "
                f"{items[i][1]}")
    return results


def _window_lane_verdicts(vals, chain_id, lanes_all, sigs_all, per_commit):
    """Per-lane verdicts for a window's collected lanes.

    Builds the merged structured batch (one template group per
    block's commit) when the expanded device path will consume it and
    the commits' values fit the vectorized layout — hostile values
    (e.g. a timestamp past int64) get full bytes instead, WITHOUT
    tripping the device-failure cooldown, mirroring
    ValidatorSet._commit_msgs. The verify ladder itself (structured →
    bytes → host, device-failure degradation, logging) is owned by
    ValidatorSet._batch_verify_lanes — one copy for every call site."""
    with tracing.TRACER.span(tracing.VERIFY_SIGN_BATCH,
                             lanes=len(lanes_all)):
        msgs = vals.structured_or_bytes(
            lanes_all,
            lambda pick: MergedSignBatch([
                CommitSignBatch(chain_id, c, slots, cols)
                for c, slots, cols in _picked(per_commit, pick)
            ]),
            lambda pick: [c.vote_sign_bytes(chain_id, s)
                          for c, slots, _ in _picked(per_commit, pick)
                          for s in slots],
        )
    from ..crypto.tpu import ledger as tpu_ledger

    with tpu_ledger.workload("fastsync"):
        _, verdicts = vals._batch_verify_lanes(lanes_all, msgs,
                                               sigs_all)
    return verdicts


def _picked(per_commit, pick):
    """per_commit with each commit's slots cut to those among the
    window's lanes at positions `pick` (ascending; None: all of them):
    the ed25519 slots, or the others, of a set of several key types. A
    commit left with no slot drops out."""
    if pick is None:
        return per_commit
    bounds = np.cumsum([0] + [len(slots) for _, slots, _ in per_commit])
    cuts = np.searchsorted(pick, bounds)
    return [(c, slots[pick[cuts[j]:cuts[j + 1]] - bounds[j]], cols)
            for j, (c, slots, cols) in enumerate(per_commit)
            if cuts[j + 1] > cuts[j]]


def window_items(blocks) -> tuple[list[tuple], list]:
    """((block_id, height, commit) per verifiable block, the built
    PartSet per block) of a peeked window: block i is verified with
    block i+1's LastCommit. The part sets ride along so the apply loop
    reuses them for save_block — make_part_set is a full-block
    serialization and must run ONCE per block, in the executor."""
    items, parts_list = [], []
    for i in range(len(blocks) - 1):
        first, second = blocks[i], blocks[i + 1]
        parts = first.make_part_set()
        bid = BlockID(first.hash(), parts.header())
        items.append((bid, first.header.height, second.last_commit))
        parts_list.append(parts)
    return items, parts_list


class WindowPipeline:
    """The verify-ahead engine one fast-sync reactor owns: hands out a
    window's verdicts (from a matching in-flight prefetch when one
    exists) and launches the NEXT window's verification concurrently
    with whatever the caller does next (executing the current window's
    blocks). Persistence order is untouched — this schedules the same
    verification earlier, nothing else."""

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self._prefetch: tuple | None = None  # (key, future, blocks)
        self.prefetch_hits = 0

    @staticmethod
    def window_key(vals, blocks) -> tuple:
        """Identity of a verification window, computed from the RAW
        blocks (never via window_items — that serializes every block
        into a part set, far too heavy for an on-loop key probe): the
        valset it verified against plus the exact block/commit objects
        consumed. Object identity is safe because the prefetch entry
        itself holds the blocks, so ids cannot be recycled while it is
        alive."""
        return (vals.hash(),
                tuple(b.header.height for b in blocks[:-1]),
                tuple(id(b) for b in blocks[:-1]),
                tuple(id(b.last_commit) for b in blocks[1:]))

    def reset(self) -> None:
        """Pool replaced (statesync handoff etc.): any in-flight
        prefetch is over stale blocks."""
        self._prefetch = None

    @staticmethod
    def _verify_window_job(vals, chain_id, blocks):
        """The executor-side unit: build the window's items + part
        sets (the make_part_set serialization per block lives HERE,
        off the event loop) and batch-verify. Returns (items,
        parts_list, results) so the consumer — prefetch hit or not —
        reuses both instead of re-serializing the window."""
        with tracing.TRACER.span(tracing.VERIFY_WINDOW,
                                 blocks=len(blocks) - 1):
            items, parts_list = window_items(blocks)
            return (items, parts_list,
                    _batch_verify_window(vals, chain_id, items))

    @staticmethod
    def _retrieve_stale(fut) -> None:
        """Done-callback for a DISCARDED prefetch (valset change /
        re-fetched window): retrieve + log its exception so a failed
        job neither vanishes silently nor leaves 'exception was never
        retrieved' noise at GC (the PR-7 singleflight convention)."""
        exc = fut.exception() if not fut.cancelled() else None
        if exc is not None:
            logger.warning("discarded verify-ahead window failed: %r",
                           exc)

    async def verdicts(self, vals, chain_id, blocks):
        """This window's (items, part sets, per-block verdicts):
        consumed from a matching prefetch when one is in flight, else
        verified now — item/part-set building AND the device batch run
        in an executor thread either way, so neither freezes the event
        loop (gossip/timeouts keep running)."""
        key = self.window_key(vals, blocks)
        pf, self._prefetch = self._prefetch, None
        if pf is not None and pf[0] == key:
            self.prefetch_hits += 1
            return await pf[1]
        if pf is not None:
            # stale (valset changed / window shifted): discarded, but
            # never silently — see _retrieve_stale
            pf[1].add_done_callback(self._retrieve_stale)
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            None, tracing.TRACER.wrap(self._verify_window_job),
            vals, chain_id, blocks)

    def start_ahead(self, vals, chain_id, peek, skip: int) -> None:
        """Launch the NEXT window's commit verification concurrently
        with the apply loop about to run: `peek(n)` returns up to n
        contiguous buffered blocks, `skip` is the length of the window
        just verified (its last block is the next window's first)."""
        if not self.enabled or self._prefetch is not None:
            return
        ahead = peek(skip - 1 + BATCH_WINDOW + 1)
        nxt = ahead[skip - 1:]
        if len(nxt) < 2:
            return
        key = self.window_key(vals, nxt)
        fut = asyncio.get_running_loop().run_in_executor(
            None, tracing.TRACER.wrap(self._verify_window_job),
            vals, chain_id, nxt)
        self._prefetch = (key, fut, nxt)


class Refusal(NamedTuple):
    """The block a window's verification refused: its index in the
    window, its height and the error. Block index+1 carried the
    LastCommit it was checked with."""

    index: int
    height: int
    error: Exception


async def sync_window(pipeline: WindowPipeline, state, blocks, peek,
                      block_store, block_exec, before_block, after_block):
    """One pass of fast sync's verify-apply loop (reference
    blockchain/v0/reactor.go poolRoutine's trySync, batched): block i
    of `blocks` is verified with block i+1's LastCommit, so with W+1
    blocks W are verifiable, in one signature batch while the set
    holds still. While this window's blocks go through save_block and
    apply_block, the NEXT window's batch verifies concurrently
    (verify-ahead; `peek(n)` returns up to n contiguous buffered
    blocks), so steady-state catch-up pays max(verify, apply) a window
    instead of their sum.

    Stops at the first refused block, and after the first block that
    moved the validator set: the remaining verdicts were computed
    against the wrong set, so those blocks stay with the caller for
    the next pass (any window verified ahead is stale too: its key
    carries the old set's hash, so the next pass discards it and
    verifies under the new set).

    `before_block(block)` and `after_block(state, block)` bracket each
    block's save and apply (the reactor's pool and metrics; a bench's
    clock). Returns (state, blocks applied, Refusal or None)."""
    vals = state.validators
    chain_id = state.chain_id
    items, parts_list, results = await pipeline.verdicts(
        vals, chain_id, blocks)
    pipeline.start_ahead(vals, chain_id, peek, len(blocks))

    applied = 0
    assumed_vals_hash = vals.hash()
    for i, err in enumerate(results):
        if err is not None:
            return state, applied, Refusal(i, items[i][1], err)
        first = blocks[i]
        before_block(first)
        # the part set built (off-loop) by the verify job: never
        # re-serialize a full block on the event loop
        block_store.save_block(first, parts_list[i],
                               blocks[i + 1].last_commit)
        state, _ = await block_exec.apply_block(state, items[i][0], first)
        applied += 1
        after_block(state, first)
        if state.validators.hash() != assumed_vals_hash:
            # what the cut throws away: verified - applied blocks of
            # this launch, and the window verified ahead
            tracing.TRACER.begin(
                tracing.SYNC_WINDOW_CUT, applied=applied,
                verified=len(results), height=first.header.height).end()
            break
    return state, applied, None
