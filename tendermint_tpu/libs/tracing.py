"""Always-on span tracer: per-height consensus timelines + device-
pipeline stage attribution.

An end-to-end commit-verify time is several times its device
execution, and the aggregate histograms in metrics.py cannot say where
the rest goes. This module is the instrument for that question —
monotonic-clock spans with parent/child
links over the hot paths:

  consensus.height                     one root span per height
    consensus.new_height               update_to_state -> round 0 (the
                                       timeout_commit wait)
    consensus.propose / .prevote / .precommit / .commit ...
      wal.fsync                        every write_sync
      store.save_block                 the block store's one batch
        store.encode_commits           the seen and the last commit as
                                       protos
        store.encode_parts             hash, meta, part rows, the
                                       store's state row (attr parts)
        store.write                    the batch's INSERTs and, as its
                                       db.write child, the COMMIT
                                       (attr rows)
      state.apply_block                ApplyBlock wall time
        state.validate                 opened on the loop around the
                                       await of the worker thread
          validate.block               the whole validate_block call,
                                       INSIDE the worker: the parent
                                       less this is the hop, both ways
            validate.basic             the block's own checks: header
                                       fields, LastCommit / Data /
                                       Evidence hashes against it
            validate.set_hashes        the header against the state:
                                       two Merkle trees over the sets
            verify.commit              one commit check, any form
              verify.collect           basics + the commit's columns:
                                       address check, lanes, tally
              verify.sign_batch        sign bytes (structured or full)
              verify.tables            table lookup by the set's held
                                       key digest (attr digest =
                                       held | hashed: once a set)
              crypto.batch             a BatchVerifier.verify call
              crypto.verify            one device verify
                crypto.pack            host byte packing (numpy)
                crypto.dispatch        kernel-launch enqueue
                crypto.device_exec     wait-until-verdicts-ready
                crypto.readback        device->host verdict copy
            validate.median_time       the BFT-time check
        state.exec                     BeginBlock, DeliverTx xN, EndBlock
        state.save_responses           ABCI responses to the state store
        state.update                   update_state (three set copies)
                                       and the start of a changed set's
                                       table warm (attr updates)
        state.app_commit               mempool lock, flush, app Commit,
                                       mempool update
        state.save                     the new State to the state store
        state.events                   event-bus publishes
  db.write                             a durable commit of SqliteDB,
                                       child of whichever span wrote it
  verify.window                        a fast-sync window in its worker:
                                       part sets, then collect /
                                       sign_batch / tables / crypto.*
  sync.window_cut                      the verify-apply loop stopped at
                                       a block that moved the set
                                       (applied of verified blocks)
  crypto.table_build                   a set's comb tables built until
                                       they are on the device (thread =
                                       warm | inline)
  crypto.table_wait                    a launch site waiting for another
                                       thread's build of its set
  admission.queue_wait                 first pending envelope -> the cut
  admission.flush                      the cut -> last verdict delivered
    crypto.verify ...                  (executor thread, via wrap)
  light.queue_wait / light.flush       the same pair for the light
                                       plane's commit-check batches
  light.request / .fetch / .plan / .step / .store_save / .detect
                                       the light proxy's per-request
                                       sites, each FOLDED (leaf with a
                                       fold_key: attrs n, busy_ns)
  light.load_programs                  once, before the first request:
                                       the plane's launch shapes loaded
  p2p.send_flush / p2p.recv_msg        wire-side attribution

Design constraints (this stays ON in production):

  * Fixed-size ring buffer (collections.deque(maxlen=N), default 16k
    spans): ending a span is one tuple append under the ring's lock;
    overflow evicts the oldest — memory is bounded no matter the load.
    One ring is one node's: a block costs it ~25 entries more since
    its verify.* / state.* / store.* / validate.* children exist
    (docs/OBSERVABILITY.md gives the horizon in heights).
  * time.perf_counter_ns() start/stop; no datetime, no wall clock.
  * A span's duration is its thread's work PLUS its wait for the
    interpreter lock. A kind registered with cpu=True (its body is one
    thread's synchronous work) also stamps time.thread_time_ns() at
    both ends and records attr `cpu_ns`; dur - cpu_ns is the time the
    thread held the span and did not run (the lock's queue; under a
    span that writes or launches, the disk or the device too). Marked
    are the twelve kinds a reader sums and no other: store.save_block,
    validate.block, verify.window, and the pure-host store.encode_*,
    validate.basic / .set_hashes / .median_time, state.update,
    verify.collect / .sign_batch, crypto.pack. A kind that wraps an
    `await` (state.validate, state.apply_block, state.exec,
    state.app_commit, admission.*, consensus.*) must NOT be marked:
    the loop thread's CPU inside it is other tasks'. A span ended on
    another thread than it began on records none. The thread clock is
    the kernel's: where it advances in ticks (10 ms on the benchmark's
    sandboxed host) ONE span's cpu_ns is 0 or whole ticks, can exceed
    its duration and means nothing; only a kind's sum over many spans
    is a reading. A read is then a system call too (~5 us, not ~0.3:
    a marked span ~15 us), so mark a per-block kind that a reader
    sums, and nothing else.
  * Task-local context via contextvars: asyncio tasks inherit the
    active span automatically. Executor threads do NOT (run_in_executor
    ignores the caller's Context), so cross-thread parenting is an
    EXPLICIT handoff: `loop.run_in_executor(None, TRACER.wrap(fn), ...)`
    captures the caller's active span and re-activates it inside the
    worker thread. This is how a crypto.verify span recorded in the
    BatchVerifier executor thread still parents under the event loop's
    consensus span.
  * Spans are per batch, per block, per commit — never per tx or per
    request: a few kinds at the tx rate would push a window's spans
    out of the ring before anyone reads them. The one site whose unit
    can be a tx (a durable db commit; the kvstore app and the tx
    indexer commit once a tx) records through leaf(), which folds a
    run of back-to-back repeats into one ring entry (attrs `n`,
    `busy_ns`, summed counters).
  * Span kinds are a closed registry: every instrumented site names a
    constant registered here (tools/check_spans.py lints for ad-hoc
    string literals). An unregistered kind raises at span start — a
    typo'd kind is a programming error, not a silent new timeline row.

Export: chrome_trace() renders the ring as Chrome trace-event JSON
("X" complete events) loadable in Perfetto / chrome://tracing; served
at GET /debug/trace?seconds=N (libs/debugsrv.py) and captured by
`tendermint-tpu debug trace` (cmd/debug.py); stage_rollup() gives the
per-kind p50/p95/p99 that GET /debug/trace/rollup serves.
"""

from __future__ import annotations

import contextvars
import itertools
import os
import struct
import threading
import time
from collections import deque
from typing import NamedTuple

# ---------------------------------------------------------------- registry

_KINDS: set[str] = set()
# kinds whose spans also carry the thread's CPU time (attr `cpu_ns`)
_CPU_KINDS: set[str] = set()


def register_kind(name: str, cpu: bool = False) -> str:
    """Register a span kind. Instrumented modules use the constants
    below; tests may register their own (namespaced `test.*`).
    `cpu=True` marks a kind whose body is ONE thread's synchronous
    work: its spans stamp time.thread_time_ns() at both ends and record
    attr `cpu_ns`, so `dur - cpu_ns` is the time the thread held the
    span and was not running (the GIL's queue, a disk, the device).
    Never mark a kind that wraps an `await`: the loop thread's CPU
    inside it is other tasks'."""
    _KINDS.add(name)
    if cpu:
        _CPU_KINDS.add(name)
    return name


def registered_kinds() -> frozenset[str]:
    return frozenset(_KINDS)


def _require_registered(kind: str) -> None:
    if kind not in _KINDS:
        raise ValueError(f"unregistered span kind {kind!r} "
                         "(register_kind / tools/check_spans.py)")


# Consensus timeline (one root per height; consensus.new_height from
# update_to_state to round 0, then step children that follow
# consensus/cstypes.py RoundStep names via consensus_step_kind()).
CONSENSUS_HEIGHT = register_kind("consensus.height")
CONSENSUS_NEW_HEIGHT = register_kind("consensus.new_height")
CONSENSUS_PROPOSE = register_kind("consensus.propose")
CONSENSUS_PREVOTE = register_kind("consensus.prevote")
CONSENSUS_PREVOTE_WAIT = register_kind("consensus.prevote_wait")
CONSENSUS_PRECOMMIT = register_kind("consensus.precommit")
CONSENSUS_COMMIT = register_kind("consensus.commit")
CONSENSUS_VOTE_BATCH = register_kind("consensus.vote_batch")
# Around it (consensus/state.py), one of each per micro-batch, never
# one per vote: a 10,000-validator height is 20,000 votes and the ring
# holds 16,384 entries. vote_queue_wait: first vote buffered -> the
# scheduler's cut (attrs lanes, shed = votes dropped at the buffer's
# bound since the last cut, cut = full | idle | cap: what ended the
# hold); vote_tally: verdicts in hand -> the last
# verified vote added under the state mutex (attrs votes, added,
# rejected). receive and has_vote are FOLDED (Tracer.leaf with a
# fold_key of the state machine's): every peer message's decode,
# peer-state marks, WAL record and handling up to the vote buffer, and
# every HasVote broadcast, summed into one entry a run (attrs n,
# busy_ns; the entry's own length is the wall time the n were spread
# over; a receive unit counts in the reactor's decode, which a burst
# does ahead of the routine, so its busy_ns can pass that length).
# load_programs: once, when consensus starts (attrs validators,
# programs, seconds): the live path's device programs, loaded before
# the first vote.
CONSENSUS_LOAD_PROGRAMS = register_kind("consensus.load_programs")
CONSENSUS_VOTE_QUEUE_WAIT = register_kind("consensus.vote_queue_wait")
CONSENSUS_VOTE_TALLY = register_kind("consensus.vote_tally")
CONSENSUS_RECEIVE = register_kind("consensus.receive")
CONSENSUS_HAS_VOTE = register_kind("consensus.has_vote")

_STEP_KINDS = {
    "PROPOSE": CONSENSUS_PROPOSE,
    "PREVOTE": CONSENSUS_PREVOTE,
    "PREVOTE_WAIT": CONSENSUS_PREVOTE_WAIT,
    "PRECOMMIT": CONSENSUS_PRECOMMIT,
    "COMMIT": CONSENSUS_COMMIT,
}


def consensus_step_kind(step_name: str) -> str:
    """RoundStep name -> registered step-span kind, for the steps
    ConsensusState._new_step enters (NEW_HEIGHT has its own span,
    opened by update_to_state; NEW_ROUND and PRECOMMIT_WAIT are passed
    through without a step of their own)."""
    return _STEP_KINDS[step_name]


# Device pipeline (crypto/batch.py, crypto/tpu/verify.py + expanded.py).
CRYPTO_BATCH = register_kind("crypto.batch")
CRYPTO_VERIFY = register_kind("crypto.verify")
CRYPTO_PACK = register_kind("crypto.pack", cpu=True)
CRYPTO_DISPATCH = register_kind("crypto.dispatch")
CRYPTO_DEVICE_EXEC = register_kind("crypto.device_exec")
CRYPTO_READBACK = register_kind("crypto.readback")
CRYPTO_HOST_VERIFY = register_kind("crypto.host_verify")
# The comb tables of a validator set (crypto/tpu/expanded.py
# get_expanded): the build until the tables are on the device, and a
# launch site held up behind another thread's build of the same set.
CRYPTO_TABLE_BUILD = register_kind("crypto.table_build")
CRYPTO_TABLE_WAIT = register_kind("crypto.table_wait")
# The host's share of an sr25519 launch's Merlin transcripts
# (crypto/tpu/sr_verify.py, inside its pack stage): since PR 36 the
# layout of each lane's blocks, no permutation (the device derives the
# challenge). Attrs `lanes`, `groups` (distinct message lengths, one
# template each) and `blocks` (the launch's block dimension: 4 unless
# a message is over 460 bytes).
CRYPTO_SR_MERLIN = register_kind("crypto.sr_merlin")

# Verify-ahead pipeline (consensus/speculation.py + crypto/tpu/
# resident.py): speculate = an ahead-of-commit verification launch,
# patch = a delta splice into the device-resident arena, reconcile =
# the commit-time serve (template match + miss fallback).
SPECULATION_SPECULATE = register_kind("speculation.speculate")
SPECULATION_PATCH = register_kind("speculation.patch")
SPECULATION_RECONCILE = register_kind("speculation.reconcile")

# Verify sites (types/validator_set.py, blockchain/verify_ahead.py):
# what a commit check costs the host around its crypto.* launch.
VERIFY_COMMIT = register_kind("verify.commit")
VERIFY_COLLECT = register_kind("verify.collect", cpu=True)
VERIFY_SIGN_BATCH = register_kind("verify.sign_batch", cpu=True)
VERIFY_TABLES = register_kind("verify.tables")
VERIFY_WINDOW = register_kind("verify.window", cpu=True)
# A batch of a set that is not all ed25519, partitioned by key type
# (types/validator_set.py _lane_split): attrs `ed25519`, `sr25519`,
# `other` = lanes of each. Never opened for an all-ed25519 set.
VERIFY_LANE_SPLIT = register_kind("verify.lane_split")
# Fast sync's verify-apply loop (blockchain/verify_ahead.py
# sync_window) stopping inside a window because the set moved.
SYNC_WINDOW_CUT = register_kind("sync.window_cut")

# A block's evidence (evidence/__init__.py Pool): `evidence.check` is
# one check_evidence call that verifies anything (attrs `evidence`,
# `heights`, `sets`, `lanes`), `evidence.collect` its host part (the
# lookups, one load of the validators and the block time a height, the
# checks before the signatures; the sign bytes are built after it,
# outside the span, in evidence/verify.py signature_errors),
# `evidence.update` the marking of a block's evidence as committed
# (attr `committed`).
EVIDENCE_CHECK = register_kind("evidence.check")
EVIDENCE_COLLECT = register_kind("evidence.collect")
EVIDENCE_UPDATE = register_kind("evidence.update")

# The verify planes' micro-batcher (crypto/collector.py), one pair per
# batch: mempool/admission.py's and light/serving.py's.
ADMISSION_QUEUE_WAIT = register_kind("admission.queue_wait")
ADMISSION_FLUSH = register_kind("admission.flush")
LIGHT_QUEUE_WAIT = register_kind("light.queue_wait")
LIGHT_FLUSH = register_kind("light.flush")
# The light proxy's path (light/proxy.py, client.py, serving.py,
# store.py). Every per-request site is FOLDED (Tracer.leaf with the
# owner's fold_key, LIGHT_FOLD_NS, LIGHT_FOLD_LOOKBACK): a proxy with
# 256 callers answers some hundred requests a second, nine units each,
# and the ring holds 16,384. attrs n, busy_ns as for every folded kind;
# the sums beside them:
#   light.request     a verified route's entry -> its reply (hits,
#                     coalesced, misses: how the plane resolved it;
#                     failed: it ended in an error)
#   light.fetch       one provider's answer and its decode into a
#                     LightBlock (bytes where the provider knows them;
#                     witness: 1 for a cross-check's fetch)
#   light.plan        the building of ONE CommitVerifyPlan (lanes;
#                     trusting: 1 for the trusted set's overlap check,
#                     0 for the new set's own +2/3)
#   light.step        one verification of an untrusted block against
#                     a trusted one, both checks (adjacent, pivots = it
#                     ended in a bisection pivot, gap = to - from,
#                     walks = hash-chain walks, counted here too)
#   light.store_save  one LightStore.save
#   light.detect      one witness cross-check of a verified block
# load_programs: once, before the first request is accepted (attrs
# programs, seconds, lanes): the plane's launch shapes.
LIGHT_REQUEST = register_kind("light.request")
LIGHT_FETCH = register_kind("light.fetch")
LIGHT_PLAN = register_kind("light.plan")
LIGHT_STEP = register_kind("light.step")
LIGHT_STORE_SAVE = register_kind("light.store_save")
LIGHT_DETECT = register_kind("light.detect")
LIGHT_LOAD_PROGRAMS = register_kind("light.load_programs")

# State machine + durability + wire. The state.* children follow
# BlockExecutor._apply_block in order.
STATE_APPLY_BLOCK = register_kind("state.apply_block")
STATE_VALIDATE = register_kind("state.validate")
# What state.validate holds (state/validation.py). validate.block is
# the whole validate_block call INSIDE the worker thread (attr height):
# state.validate less it is the executor hop, both ways. Its children
# beside verify.commit and evidence.check: the block's own checks
# (header fields, the LastCommit / Data / Evidence hashes against the
# header), the header's hashes against the state (two Merkle trees over
# the validator sets; attr validators) and the BFT-time check.
VALIDATE_BLOCK = register_kind("validate.block", cpu=True)
VALIDATE_BASIC = register_kind("validate.basic", cpu=True)
VALIDATE_SET_HASHES = register_kind("validate.set_hashes", cpu=True)
VALIDATE_MEDIAN_TIME = register_kind("validate.median_time", cpu=True)
STATE_EXEC = register_kind("state.exec")
STATE_SAVE_RESPONSES = register_kind("state.save_responses")
STATE_APP_COMMIT = register_kind("state.app_commit")
# update_state and the start of the changed set's table warm, between
# state.save_responses and state.app_commit (attr updates)
STATE_UPDATE = register_kind("state.update", cpu=True)
STATE_SAVE = register_kind("state.save")
# a validator set's membership encoded in full by the state store
# (attrs height, keys, bytes): none on a block whose set stands still
STATE_VALSET_ROW = register_kind("state.valset_row")
STATE_EVENTS = register_kind("state.events")
STORE_SAVE_BLOCK = register_kind("store.save_block", cpu=True)
# What store.save_block holds (store/__init__.py _save_block), in
# order: the seen commit and the last commit as protos (attr columnar:
# how many of the two types/sign_batch.py commit_sig_rows encoded from
# their columns, 0-2; the per-slot writer took the others), the meta,
# hash, part and store-state rows (attr parts), the batch's INSERTs and
# its COMMIT (attr rows; db.write, the COMMIT, is its child).
STORE_ENCODE_COMMITS = register_kind("store.encode_commits", cpu=True)
STORE_ENCODE_PARTS = register_kind("store.encode_parts", cpu=True)
STORE_WRITE = register_kind("store.write")
DB_WRITE = register_kind("db.write")
WAL_FSYNC = register_kind("wal.fsync")
P2P_SEND_FLUSH = register_kind("p2p.send_flush")
P2P_RECV_MSG = register_kind("p2p.recv_msg")


# ---------------------------------------------------------------- spans

_CURRENT: contextvars.ContextVar["Span | None"] = contextvars.ContextVar(
    "tm_tpu_trace_span", default=None
)

_ids = itertools.count(1)  # CPython: count.__next__ is GIL-atomic


class Span:
    """A live span. end() seals it into the tracer's ring buffer as a
    plain tuple; no reference is kept after that beyond the ring."""

    __slots__ = ("kind", "span_id", "parent_id", "tid", "t0", "attrs",
                 "_tracer", "_done", "_cpu0")

    def __init__(self, tracer: "Tracer", kind: str, parent_id: int,
                 attrs: dict | None, start_ns: int | None = None):
        self.kind = kind
        self.span_id = next(_ids)
        self.parent_id = parent_id
        self.tid = threading.get_ident()
        self.attrs = attrs
        self._tracer = tracer
        self._done = False
        self.t0 = time.perf_counter_ns() if start_ns is None else start_ns
        # the CPU interval lies inside the wall interval: cpu_ns <= dur
        # wherever the thread clock is as fine as the wall clock; where
        # it ticks (10 ms on a sandboxed host) only sums of many spans
        # mean anything
        self._cpu0 = time.thread_time_ns() if kind in _CPU_KINDS else None

    def set_attr(self, key: str, value) -> None:
        if self.attrs is None:
            self.attrs = {}
        self.attrs[key] = value

    def end(self) -> None:
        if self._done:  # idempotent: height/step spans end via two paths
            return
        self._done = True
        if self._cpu0 is not None and threading.get_ident() == self.tid:
            # a span ended on another thread has no CPU time to give
            self.set_attr("cpu_ns", time.thread_time_ns() - self._cpu0)
        t1 = time.perf_counter_ns()
        tracer = self._tracer
        tracer._append((
            self.kind, self.span_id, self.parent_id, self.tid,
            self.t0, t1 - self.t0, self.attrs,
        ))
        # tracing→metrics bridge: the same close feeds the kind's
        # Prometheus histogram (libs/metrics.py span_metrics_sink) —
        # one instrumentation point, two exports. Monitoring must
        # never take down the instrumented path, hence the blanket
        # except; the sink itself is a dict lookup + bucket scan,
        # inside the tools/check_spans.py per-span budget.
        tracer._observe(self.kind, t1 - self.t0)


class _NoopSpan:
    """Shared do-nothing span for the disabled tracer (and a safe
    parent placeholder): keeps call sites branch-free."""

    __slots__ = ()
    kind = ""
    span_id = 0
    parent_id = 0

    def set_attr(self, key, value) -> None:
        pass

    def end(self) -> None:
        pass


NOOP_SPAN = _NoopSpan()


class _QuietSpan(_NoopSpan):
    """The current span inside Tracer.quiet(): nothing begun or
    recorded beneath it reaches the ring."""

    __slots__ = ()


QUIET = _QuietSpan()


class _SpanCtx:
    """Context manager: starts a span parented on the task-local
    current span, makes it current for the body, seals it on exit."""

    __slots__ = ("_tracer", "_kind", "_attrs", "_span", "_token")

    def __init__(self, tracer, kind, attrs):
        self._tracer = tracer
        self._kind = kind
        self._attrs = attrs

    def __enter__(self) -> Span:
        self._span = self._tracer.begin(self._kind, **(self._attrs or {}))
        # disabled tracer: skip the contextvar set/reset entirely
        self._token = (None if self._span is NOOP_SPAN
                       else _CURRENT.set(self._span))
        return self._span

    def __exit__(self, *exc) -> bool:
        if self._token is not None:
            _CURRENT.reset(self._token)
        self._span.end()
        return False


class _AttachCtx:
    """Context manager: make an existing span the task-local current
    span (explicit handoff) without starting or ending anything."""

    __slots__ = ("_span", "_token")

    def __init__(self, span):
        self._span = span

    def __enter__(self):
        self._token = _CURRENT.set(self._span)
        return self._span

    def __exit__(self, *exc) -> bool:
        _CURRENT.reset(self._token)
        return False


# ---------------------------------------------------------------- tracer

DEFAULT_CAPACITY = int(os.environ.get("TM_TPU_TRACE_CAPACITY", "16384"))
# leaf(): the longest pause between two repeats that still fold into
# one entry. A tx-rate run of db commits has well under a millisecond
# of caller time between them; writes of different blocks are far
# apart and stay apart.
LEAF_FOLD_NS = 2_000_000
# leaf(fold_key=...): how many of the ring's newest entries are looked
# through for the entry the key wrote last.
LEAF_KEY_LOOKBACK = 32
# The light proxy's folded kinds: their units are whole requests,
# milliseconds apart and seconds long (256 callers queue for the one
# loop), so a run tolerates that much pause and that much overlap, and
# its entry is looked for further back (seven spans a launch are sealed
# between two units of a kind).
LIGHT_FOLD_NS = 10_000_000_000
LIGHT_FOLD_LOOKBACK = 256


class Tracer:
    """Ring-buffered span recorder. Every change to the ring (a span's
    append, leaf()'s fold into an entry, resize) and every copy of it
    happens under one lock, so a snapshot never misses a span another
    thread has sealed and entries keep the order they ended in (a
    folded entry: the order its first unit ended in)."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 enabled: bool = True):
        self.capacity = capacity
        self.enabled = enabled
        self._ring: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._dropped = 0
        self._keyed: dict = {}   # leaf(fold_key=...): key -> its entry
        # tracing→metrics bridge: fn(kind, seconds) called on every
        # span close (libs/metrics.py installs span_metrics_sink on
        # the global TRACER). None = no bridge (private test tracers).
        self.metrics_sink = None
        # eviction bridge: fn(n) on every ring overflow — feeds
        # tracing_spans_dropped_total. Same None-means-no-bridge rule.
        self.drop_sink = None

    def set_metrics_sink(self, sink) -> None:
        self.metrics_sink = sink

    def set_drop_sink(self, sink) -> None:
        self.drop_sink = sink

    @property
    def dropped(self) -> int:
        """Spans evicted from the ring since the last clear() — a
        non-zero value means snapshot() is a suffix, not the history."""
        return self._dropped

    # -- recording --

    def begin(self, kind: str, parent: Span | None = None,
              start_ns: int | None = None, **attrs) -> Span:
        """Start a span. Parent defaults to the task-local current
        span; pass `parent=` to link manually-managed spans (the
        consensus height/step timeline). `start_ns` backdates the span
        to a perf_counter_ns the caller stamped (a queue wait is only
        known at the cut). Returns NOOP_SPAN when disabled — callers
        never branch."""
        if not self.enabled:
            return NOOP_SPAN
        _require_registered(kind)
        if parent is None:
            parent = _CURRENT.get()
        if parent is QUIET:
            return NOOP_SPAN
        return Span(self, kind, parent.span_id if parent else 0,
                    attrs or None, start_ns)

    def leaf(self, kind: str, start_ns: int, fold_key=None,
             parent: Span | None = None, fold_ns: int = LEAF_FOLD_NS,
             lookback: int = LEAF_KEY_LOOKBACK, **sums) -> None:
        """Record a finished childless span [start_ns, now) under the
        current span (or `parent`) — the form for a site whose unit
        may be a tx (a durable db commit) or a peer message. A repeat
        that follows the ring's newest entry — same kind, parent and
        thread, begun within LEAF_FOLD_NS of its end — extends that
        entry instead of adding one: `n` counts the repeats, `busy_ns`
        sums their own durations (the entry's duration then includes
        the caller's time between them) and each of `sums` adds up. A
        tx-rate writer thus costs the ring one entry per run, not one
        per tx.

        `fold_key` (any hashable the owner picks): the repeat extends
        the entry this key wrote last instead of the newest — for a
        site whose repeats interleave with other spans (a vote's
        receive between the scheduler's batches). The entry is looked
        for among the ring's `lookback` newest, so a run is cut by a
        pause, by a new parent, or by that many spans sealed since it
        began; it keeps the place in the ring its first unit took.
        `fold_ns` is the longest pause a run survives and, for a keyed
        run, the most its units may overlap (the light proxy's units
        are whole requests: LIGHT_FOLD_NS)."""
        if not self.enabled:
            return
        _require_registered(kind)
        t1 = time.perf_counter_ns()
        dur = t1 - start_ns
        if parent is None:
            parent = _CURRENT.get()
        if parent is QUIET:
            return
        pid = parent.span_id if parent else 0
        tid = threading.get_ident()
        with self._lock:
            ring = self._ring
            at = 1 if ring else 0
            if fold_key is None:
                last = ring[-1] if ring else None
                lo = 0    # a repeat begins after the newest has ended
            else:
                last = self._keyed.get(fold_key)
                at = next((i for i in range(
                    1, min(len(ring), lookback) + 1)
                    if ring[-i] is last), 0) if last is not None else 0
                lo = -fold_ns   # units of one owner may overlap
            folds = (at and last[0] == kind
                     and last[2] == pid and last[3] == tid
                     and lo <= start_ns - (last[4] + last[5])
                     <= fold_ns)
            if folds:
                attrs = dict(last[6] or ())
                attrs["n"] = attrs.get("n", 1) + 1
                attrs["busy_ns"] = attrs.get("busy_ns", last[5]) + dur
                for k, v in sums.items():
                    attrs[k] = attrs.get(k, 0) + v
                t0 = min(last[4], start_ns)
                rec = (kind, last[1], pid, tid, t0,
                       max(t1, last[4] + last[5]) - t0, attrs)
                ring[-at] = rec
            else:
                rec = (kind, next(_ids), pid, tid, start_ns, dur, sums)
            if fold_key is not None:
                self._keyed[fold_key] = rec
        if not folds:
            self._append(rec)
        self._observe(kind, dur)

    def _append(self, rec: tuple) -> None:
        """Seal one finished span into the ring. Overflow accounting:
        deque(maxlen=N) evicts silently, so a truncated timeline would
        be indistinguishable from a complete one."""
        with self._lock:
            full = len(self._ring) >= self.capacity
            self._ring.append(rec)
        if full:
            self._count_drop()

    def _observe(self, kind: str, dur_ns: int) -> None:
        """Feed one closed span to the metrics bridge, if there is one."""
        sink = self.metrics_sink
        if sink is not None:
            try:
                sink(kind, dur_ns / 1e9)
            except Exception:
                pass

    def _count_drop(self) -> None:
        self._dropped += 1
        dsink = self.drop_sink
        if dsink is not None:
            try:
                dsink(1)
            except Exception:
                pass

    def span(self, kind: str, **attrs) -> _SpanCtx:
        """`with TRACER.span(KIND, k=v): ...` — the instrumented-site
        form. Nested spans parent automatically via the task context."""
        return _SpanCtx(self, kind, attrs)

    def current(self) -> Span | None:
        return _CURRENT.get()

    def attach(self, span: Span | None) -> _AttachCtx:
        """Make `span` current for a block — used to hang with-block
        children under a manually-managed span (e.g. the commit step
        span during finalize) regardless of which task runs the code."""
        return _AttachCtx(span)

    def quiet(self) -> _AttachCtx:
        """`with TRACER.quiet(): ...` — the body's spans and leaves are
        not recorded. For a site that is itself ONE unit of a folded
        kind (a plan the light proxy builds, a block it saves): its
        body's per-commit spans would each cost the ring an entry a
        request, which is what the fold is there to spare."""
        return _AttachCtx(QUIET)

    def wrap(self, fn):
        """Explicit executor handoff: capture the caller's active span
        NOW; the returned callable re-activates it in whatever thread
        runs fn. `loop.run_in_executor(None, TRACER.wrap(f), *a)`."""
        parent = _CURRENT.get()

        def _with_parent(*args, **kwargs):
            token = _CURRENT.set(parent)
            try:
                return fn(*args, **kwargs)
            finally:
                _CURRENT.reset(token)

        return _with_parent

    # -- reading --

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._keyed.clear()
            self._dropped = 0

    def resize(self, capacity: int) -> None:
        """Give the ring another size, keeping the newest spans. The
        default holds one node's recent heights; a process that hosts
        several nodes (sim/scenario.py) asks for one node's worth
        each."""
        with self._lock:
            self._ring = deque(self._ring, maxlen=capacity)
            self.capacity = capacity

    def __len__(self) -> int:
        return len(self._ring)

    def snapshot(self, seconds: float | None = None) -> list[tuple]:
        """Finished spans, oldest first; `seconds` keeps only spans
        that ENDED within the trailing window."""
        with self._lock:
            recs = list(self._ring)
        if seconds is None:
            return recs
        cutoff = time.perf_counter_ns() - int(seconds * 1e9)
        return [r for r in recs if r[4] + r[5] >= cutoff]

    def stage_rollup(self, seconds: float | None = None,
                     prefix: str | None = None) -> dict[str, dict]:
        """Per-kind latency rollup {kind: {count, p50_ms, p95_ms,
        p99_ms, total_ms}} over the ring (optionally windowed /
        prefix-filtered) — what GET /debug/trace/rollup serves."""
        by_kind: dict[str, list[int]] = {}
        for r in self.snapshot(seconds):
            if prefix is not None and not r[0].startswith(prefix):
                continue
            by_kind.setdefault(r[0], []).append(r[5])
        out: dict[str, dict] = {}
        for kind, durs in sorted(by_kind.items()):
            durs.sort()
            n = len(durs)

            def pct(p):
                return durs[min(n - 1, int(p * n))] / 1e6

            out[kind] = {
                "count": n,
                "p50_ms": round(pct(0.50), 4),
                "p95_ms": round(pct(0.95), 4),
                "p99_ms": round(pct(0.99), 4),
                "total_ms": round(sum(durs) / 1e6, 4),
            }
        return out


# Process-global tracer — the instrument every module records into.
TRACER = Tracer()


# ---------------------------------------------------------------- origin tags
#
# Cross-node trace context. A compact binary tag rides the consensus
# wire messages that define the block lifecycle (Proposal, BlockPart,
# Vote): the sender stamps (height, round, its node label, the span id
# active at send time), the receiver rehydrates the tag into the attrs
# of its live p2p.recv_msg span. A part's recv span on node B thus
# names its send span on node A — zero new hot-path span sites, and
# peers that never set the field are untouched (the wire field is
# optional; old decoders skip it as an unknown proto field).

_ORIGIN_VERSION = 1
_ORIGIN_HDR = struct.Struct(">BQIQ")  # version, height, round, span_id
_ORIGIN_MAX_NODE = 64  # label bytes cap: tags stay wire-cheap


class OriginTag(NamedTuple):
    height: int
    round: int
    node: str
    span_id: int


def encode_origin(height: int, round_: int, node: str,
                  span_id: int = 0) -> bytes:
    """Binary origin tag: 21-byte fixed header + UTF-8 node label
    (truncated to 64 bytes). Total ≤ 85 bytes per stamped message."""
    label = node.encode("utf-8", "replace")[:_ORIGIN_MAX_NODE]
    return _ORIGIN_HDR.pack(
        _ORIGIN_VERSION, height & (2**64 - 1), round_ & (2**32 - 1),
        span_id & (2**64 - 1)) + label


def decode_origin(data: bytes | None) -> OriginTag | None:
    """Parse an origin tag; never raises. None on absent/short/
    unknown-version payloads — a garbled tag degrades to 'no tag',
    it must not take down message decode."""
    if not data or len(data) < _ORIGIN_HDR.size:
        return None
    try:
        ver, height, round_, span_id = _ORIGIN_HDR.unpack_from(data)
        if ver != _ORIGIN_VERSION:
            return None
        node = data[_ORIGIN_HDR.size:].decode("utf-8", "replace")
        return OriginTag(height, round_, node, span_id)
    except Exception:
        return None


def origin_stamp(node: str, height: int, round_: int) -> bytes:
    """Send-side: build the tag for an outgoing lifecycle message,
    capturing the task-local active span (0 if none — the node/height/
    round triple still carries the cross-node link)."""
    cur = _CURRENT.get()
    return encode_origin(height, round_, node,
                         cur.span_id if cur is not None else 0)


def rehydrate_origin(data: bytes | None) -> OriginTag | None:
    """Recv-side: decode an incoming tag and fold it into the attrs of
    the live current span (the p2p.recv_msg span wrapping reactor
    dispatch), linking this receive to the sender's send-side span."""
    tag = decode_origin(data)
    if tag is None:
        return None
    cur = _CURRENT.get()
    if cur is not None:
        cur.set_attr("origin_node", tag.node)
        cur.set_attr("origin_height", tag.height)
        cur.set_attr("origin_round", tag.round)
        if tag.span_id:
            cur.set_attr("origin_span", tag.span_id)
    return tag


# ---------------------------------------------------------------- export

_PID = os.getpid()


def chrome_trace(records: list[tuple], meta: dict | None = None) -> dict:
    """Chrome trace-event JSON (the `traceEvents` array object form)
    from snapshot() tuples: one "X" (complete) event per span, ts/dur
    in microseconds, parent links + attributes under args. Loads
    directly in Perfetto / chrome://tracing; nesting renders from
    ts/dur containment per (pid, tid) track, and args.parent_id gives
    exact cross-thread lineage. `meta` (ring capacity, drop counter,
    clock anchor...) lands under a top-level "tm_tpu" key — viewers
    ignore unknown top-level keys, collectors read it."""
    events = []
    for kind, span_id, parent_id, tid, t0, dur, attrs in records:
        args = {"span_id": span_id}
        if parent_id:
            args["parent_id"] = parent_id
        if attrs:
            args.update(attrs)
        events.append({
            "name": kind,
            "cat": kind.partition(".")[0],
            "ph": "X",
            "ts": t0 / 1e3,
            "dur": dur / 1e3,
            "pid": _PID,
            "tid": tid,
            "args": args,
        })
    out = {"traceEvents": events, "displayTimeUnit": "ms"}
    if meta is not None:
        out["tm_tpu"] = meta
    return out


def light_leaf(kind: str, start_ns: int, **sums) -> None:
    """One unit of a per-request site of the light proxy's path, folded
    into the process's run of that kind (the kind is the fold key)."""
    TRACER.leaf(kind, start_ns, fold_key=kind, fold_ns=LIGHT_FOLD_NS,
                lookback=LIGHT_FOLD_LOOKBACK, **sums)
