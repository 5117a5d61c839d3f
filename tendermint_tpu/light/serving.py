"""Light-client serving plane (ROADMAP item 4; no reference
equivalent — the reference light proxy walks the client serially, one
commit-verify per bisection pivot per request).

A skipping verify is two >1/3-power commit checks — exactly the shape
the batched verify kernel already accelerates — yet a proxy serving N
concurrent read-mostly clients used to pay N independent serial
verification walks. The ServingPlane here sits between the LightProxy
RPC surface (one or many workers — ServingPool; `cmd light` runs one)
and the light ``Client`` and turns N concurrent requests into few wide
launches:

  * **request coalescing + verified-header cache** — a singleflight
    map keyed by height makes concurrent requests for the same height
    pay ONE verification, and a trusting-period-aware in-memory LRU
    over the trusted ``LightStore`` makes the second client hitting a
    verified height cost a dict lookup, not a device launch;

  * **batched skipping verify** — a micro-batching collector
    (crypto/collector.py's size-or-deadline flusher, weighing a plan
    by its lanes) takes ``types/validator_set.py`` CommitVerifyPlans
    from independent requests AND from both checks of one bisection
    step (the trusted-overlap check and the new set's own +2/3 check
    run concurrently) and executes them as wide ed25519 launches
    through crypto/batch.py's one guarded launch — breaker-aware with
    host fallback, one known-answer sentinel lane per device launch (a
    NaN-ing kernel fails the sentinel and the launch re-runs on host
    instead of failing requests on wrong verdicts);

  * **ONE launch shape, loaded at start** — every device launch of
    the plane has ``batch_max`` lanes (1,024 as shipped: with the
    callers a proxy is run for, the loop hands the collector plans
    faster than a launch takes, so a cut is full whatever the
    deadline, and a narrower second shape cost every start a second
    program: PERF.md §6, PR 42). The sentinel's lane is counted in, so
    a cut holds at most batch_max - 1 signature lanes; a plan wider
    than that goes alone and is split onto the same shape; the
    trust root's own check included (``ServingPlane.initialize``), and
    ``ServingPlane.load_programs`` loads it off the loop before the
    first request is accepted (span ``light.load_programs``): after
    start nothing on the light path compiles, whatever a cut holds. A
    verify that met a cold shape held the executor, and every request
    behind it, for the minute or two of a compile;

  * **bounded pending-verify backlog** — the collector's parked +
    in-verify commit checks are the ``light.pending_verify`` entry in
    the overload QUEUES catalog: at the bound the NEWEST request is
    shed with a 429-style error, so a request flood dies at the
    plane, not in the event loop (and never behind a wedged device —
    the ``light.verify`` failpoint's `delay` shape is the proof).

The plane preserves the Client's verification semantics exactly —
the same three cases of a height against the trusted store (at or
above the latest trusted block: forwards from it; below the first:
backwards by hash linkage; between them: forwards BY SIGNATURE from
the closest trusted block below, ``LightStore.light_block_before``,
as the reference's verifyLightBlock does), same bisection pivots,
same error classes, same witness cross-checking after the target
verifies, same trusted-store writes — only the signature work is
pooled.
"""

from __future__ import annotations

import asyncio
import collections
import itertools
import logging
import time

import numpy as np

from ..crypto.collector import BacklogFull, BatchCollector
from ..libs import tracing
from ..types.validator_set import CommitVerifyPlan, VerificationError
from .errors import (
    DivergenceError,
    LightClientError,
    NewValSetCantBeTrustedError,
    OutsideTrustingPeriodError,
    VerificationFailedError,
)
from .types import LightBlock

logger = logging.getLogger("light.serving")

PENDING_VERIFY_QUEUE = "light.pending_verify"

# Shed reasons — the closed label set of light_shed_total
# (tools/check_backpressure.py lints call sites against it).
SHED_QUEUE_FULL = "queue_full"
SHED_REASONS = (SHED_QUEUE_FULL,)


class LightServingShedError(LightClientError):
    """Pending-verify backlog full: the newest request is shed (429 at
    the proxy) — transient backpressure, NOT a verification verdict."""

    def __init__(self, depth: int, limit: int):
        super().__init__(
            f"light serving plane overloaded: {depth} commit checks "
            f"pending (limit {limit}); retry later")


# -- the process-global active plane (the /status `light` check) ------

_ACTIVE_PLANE: "ServingPlane | None" = None


def active_plane() -> "ServingPlane | None":
    """The most recently built (not yet closed) plane in this process
    — what libs/debugsrv.py's HealthMonitor reports under the `light`
    check. Several in-process test planes replace each other, same
    stance as the metric/controller singletons."""
    return _ACTIVE_PLANE


class VerifiedHeaderCache:
    """Trusting-period-aware LRU over verified LightBlocks.

    Backs the trusted LightStore with an O(1) hot path: the store
    round-trips JSON per get, this returns the live object. Entries
    whose header time has left the trusting period are evicted on
    read — a block outside its period must not be served as trusted
    (its valset may have long unbonded), even though it still sits in
    the persistent store."""

    def __init__(self, max_entries: int, period_ns: int):
        self.max_entries = max(1, max_entries)
        self.period_ns = period_ns
        self._d: collections.OrderedDict[int, LightBlock] = \
            collections.OrderedDict()

    def __len__(self) -> int:
        return len(self._d)

    def get(self, height: int, now_ns: int) -> LightBlock | None:
        lb = self._d.get(height)
        if lb is None:
            return None
        if lb.time() + self.period_ns <= now_ns:
            del self._d[height]
            return None
        self._d.move_to_end(height)
        return lb

    def put(self, lb: LightBlock, now_ns: int) -> None:
        if lb.time() + self.period_ns <= now_ns:
            return  # already expired: never cache
        self._d[lb.height()] = lb
        self._d.move_to_end(lb.height())
        while len(self._d) > self.max_entries:
            self._d.popitem(last=False)

    def clear(self) -> None:
        self._d.clear()


class LightVerifyCollector(BatchCollector):
    """Micro-batching commit-check collector: the light face of
    crypto/collector.py's BatchCollector. An item is a
    CommitVerifyPlan, weighing its signature lanes — batches cut once
    ``batch_max`` LANES have accumulated or ``flush_ms`` after the
    first pending plan, and a plan wider than ``batch_max`` goes
    alone. Every plan's triples run through ONE guarded general-kernel
    launch (crypto/batch.py: cross-plan batches mix validator sets, so
    per-lane keys are the right tool, not any one set's expanded
    tables) and the per-lane verdicts scatter back per plan. A plan
    with any invalid lane gets the same VerificationError its inline
    execute() would raise — one request's lying provider never poisons
    the verdicts of the batchmates.

    A device launch has ``shapes.lanes`` lanes, the sentinel's lane
    counted in: the cut leaves that lane free (``batch_max - 1``
    signature lanes), and lanes past it (a plan that went alone) go
    as further launches of the same shape."""

    def __init__(self, batch_max: int = 1024, flush_ms: float = 2.0,
                 pending_max: int = 1024,
                 device_threshold: int | None = None, controller=None):
        from ..crypto import batch as cbatch
        from ..crypto.tpu.verify import LaunchShapes

        self.device_threshold = cbatch._DEVICE_THRESHOLD \
            if device_threshold is None else device_threshold
        self.shapes = LaunchShapes(max(2, batch_max))
        super().__init__(
            queue="light.pending_verify", limit=pending_max,
            batch_max=max(1, batch_max - 1), flush_ms=flush_ms,
            run_batch=lambda plans: self._verify_jobs(plans),
            span_kinds=(tracing.LIGHT_QUEUE_WAIT, tracing.LIGHT_FLUSH),
            controller=controller)

    @property
    def pending_max(self) -> int:
        return self.limit

    def pending_lanes(self) -> int:
        return self._pending_weight

    async def check(self, plan: CommitVerifyPlan) -> None:
        """Queue `plan` for the next coalesced launch; returns when
        every lane verified, raises VerificationError (bad slots named
        exactly like the inline path) otherwise. Raises
        LightServingShedError (shed-newest) at the backlog bound —
        UNcounted: one shed REQUEST may park two plans (the gathered
        checks of a non-adjacent step), so the plane counts sheds once
        per request, not here per plan."""
        try:
            verdicts = await self.submit(plan, len(plan))
        except BacklogFull as e:
            raise LightServingShedError(e.depth, e.limit) from None
        plan.raise_invalid(verdicts)

    # -- the coalesced verify launch (executor thread) -----------------

    def _verify_jobs(self, plans: list[CommitVerifyPlan]
                     ) -> list[np.ndarray]:
        """Flatten every plan's triples into one launch, scatter the
        per-lane verdicts back per plan."""
        triples: list[tuple] = []
        spans: list[tuple[int, int]] = []
        for plan in plans:
            t = plan.triples()
            spans.append((len(triples), len(t)))
            triples.extend(t)
        verdicts = self._verify_triples(triples)
        return [verdicts[off:off + n] for off, n in spans]

    def _verify_triples(self, triples: list[tuple]) -> np.ndarray:
        from ..crypto import batch as cbatch
        from ..libs import failpoints
        from ..libs.metrics import light_metrics

        met = light_metrics()
        n = len(triples)
        met.batch_lanes.observe(n)
        t0 = time.perf_counter()
        try:
            try:
                failpoints.hit("light.verify")
                injected = False
            except failpoints.FailpointError:
                # injected launch failure: degrade to the host oracle,
                # exactly like a raising device launch
                injected = True
            out = np.zeros(n, bool)
            ed: list[int] = []
            for i, (pk, m, s) in enumerate(triples):
                if pk.type_name == "ed25519":
                    ed.append(i)
                    continue
                # non-ed25519 lanes (sr25519/secp256k1 validators)
                # verify on host per key — rare in practice, never
                # worth a second kernel here
                try:
                    out[i] = pk.verify_signature(m, s)
                except Exception:
                    out[i] = False
            # one launch holds the shape less the sentinel's lane
            room = self.shapes.lanes - 1
            for part in [ed[lo:lo + room]
                         for lo in range(0, len(ed), room)] or [[]]:
                backend = "host"
                if part:
                    lanes = ([triples[i][0].bytes() for i in part],
                             [triples[i][1] for i in part],
                             [triples[i][2] for i in part])
                    if injected:
                        dv, backend = cbatch.host_ed25519_launch(*lanes)
                    else:
                        dv, backend = cbatch.guarded_ed25519_launch(
                            *lanes, workload="light",
                            device_threshold=self.device_threshold,
                            shapes=self.shapes)
                    out[np.asarray(part)] = dv
                cbatch.note_plane_launch(met.verify_launches, backend)
            return out
        finally:
            met.verify_seconds.observe(time.perf_counter() - t0)


class ServingPlane:
    """The shared verification plane N proxy workers run requests
    through. One plane owns one light Client (and its trusted store);
    requests enter via get_verified()."""

    def __init__(self, client, config=None, controller=None):
        from ..config import LightConfig

        cfg = config or LightConfig()
        cfg.validate_basic()
        self.client = client
        self.config = cfg
        self.cache = VerifiedHeaderCache(
            cfg.cache_size, client.trust_options.period_ns)
        self.collector = LightVerifyCollector(
            batch_max=cfg.batch_max, flush_ms=cfg.flush_ms,
            pending_max=cfg.pending_max, controller=controller)
        self._inflight: dict[int, asyncio.Task] = {}
        # running tallies for the /status `light` check (metric
        # counters mirror these with labels)
        self.requests = 0
        self.coalesced = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self.store_hits = 0     # LRU misses the trusted store served
        self.verifications = 0  # singleflight verifications started
        self.steps = 0          # verifications of one block by signature
        self.hash_walks = 0     # heights served by the backwards walk
        self.sheds: dict[str, int] = {r: 0 for r in SHED_REASONS}
        # An observer's list (None: nobody listens): the plane's
        # decisions in the order its loop made them, as tuples
        #   ("verify", rid, height)          a verification begins
        #   ("step", rid, trusted, untrusted, trusting lanes, own
        #    lanes, "ok" | the error's class name)
        #   ("walk", rid, height)            a backwards walk's end
        #   ("done", rid, height served or None, error class or None)
        # so that a model of the verification rules (the benchmark's,
        # the tests') can be replayed beside them. Nothing reads it
        # here.
        self.journal: list | None = None
        self._told = (0, 0, 0)   # resolved_since()'s last reading
        self._rids = itertools.count(1)
        global _ACTIVE_PLANE
        _ACTIVE_PLANE = self

    async def load_programs(self) -> int:
        """Load the collector's launch shape off the loop, before the
        first request is accepted (ServingPool.start): afterwards no
        launch of the light path compiles. Returns the programs
        loaded. A load that fails is logged and left to the first
        launch."""
        from ..crypto import batch as cbatch

        shapes = self.collector.shapes
        t0 = time.perf_counter_ns()
        try:
            programs = await asyncio.get_running_loop().run_in_executor(
                None, cbatch.load_ed25519_programs, shapes)
        except Exception:
            logger.exception("loading the light plane's programs "
                             "failed; the first launches compile")
            return 0
        if programs:
            seconds = (time.perf_counter_ns() - t0) / 1e9
            tracing.TRACER.begin(
                tracing.LIGHT_LOAD_PROGRAMS, start_ns=t0,
                programs=programs, seconds=round(seconds, 3),
                lanes=shapes.lanes).end()
            logger.info("light plane: %d programs loaded in %.1f s "
                        "(%d lanes)", programs, seconds, shapes.lanes)
        return programs

    async def initialize(self) -> LightBlock:
        """Pin the client's trust root with its own +2/3 check run
        through the collector: a launch of the plane's shape like every
        other (the serial client's inline check would be a launch of a
        bucket of its own, one more program at every start)."""
        return await self.client.initialize(check=self.collector.check)

    def resolved_since(self) -> dict:
        """How the requests since the last call were resolved (the
        sums a light.request span carries): hits = served from the
        LRU or the trusted store, coalesced = joined a verification
        in flight, misses = began one."""
        now = (self.cache_hits + self.store_hits, self.coalesced,
               self.verifications)
        was, self._told = self._told, now
        return dict(zip(("hits", "coalesced", "misses"),
                        (a - b for a, b in zip(now, was))))

    def _note(self, *event) -> None:
        if self.journal is not None:
            self.journal.append(event)

    def close(self) -> None:
        self.collector.close()
        for task in self._inflight.values():
            task.cancel()
        self._inflight.clear()
        global _ACTIVE_PLANE
        if _ACTIVE_PLANE is self:
            _ACTIVE_PLANE = None

    # -- the request entry point ---------------------------------------

    async def get_verified(self, height: int = 0) -> LightBlock:
        """Verified LightBlock at `height` (0 = the primary's latest).
        Coalesces with any in-flight verification of the same height;
        sheds (LightServingShedError) when the pending-verify backlog
        is at its bound and this request would start a NEW
        verification."""
        from ..libs.metrics import light_metrics

        met = light_metrics()
        self.requests += 1
        now_ns = self.client.now_fn()
        if height:
            lb = self.cache.get(height, now_ns)
            if lb is not None:
                self.cache_hits += 1
                met.cache_hits.inc()
                return lb
            self.cache_misses += 1
            met.cache_misses.inc()
            # trusted-store probe BEFORE the admission gate: a height
            # already verified and still inside its period is a READ,
            # not new verification work — it serves even with the
            # plane fully saturated (LRU refilled in passing), and
            # without spawning a singleflight task
            stored = self.client.store.get(height)
            if stored is not None and stored.time() + \
                    self.client.trust_options.period_ns > now_ns:
                self.store_hits += 1
                self.cache.put(stored, now_ns)
                return stored
        task = self._inflight.get(height)
        if task is not None and not task.done():
            # join the in-flight verification: no new device work, no
            # queue growth — the whole point of the singleflight map
            self.coalesced += 1
            met.requests_coalesced.inc()
            return await self._await_counted(task)
        if self.collector.saturated():
            # shed at ADMISSION: a flood of distinct heights must die
            # here with a cheap 429, not deep inside a bisection.
            # This gate covers backwards walks too — they never enter
            # the pending-verify queue, but each one is new work
            # (primary fetches per uncached interim), and a scrape-
            # the-history flood of distinct cold heights must not
            # amplify into unbounded concurrent walks while the
            # plane is already saturated. Store-resident heights
            # were served above, before the gate.
            self._count_shed(SHED_QUEUE_FULL)
            raise LightServingShedError(self.collector.depth(),
                                        self.collector.pending_max)
        self.verifications += 1
        task = asyncio.get_running_loop().create_task(
            self._verify_height(height, now_ns),
            name=f"light-verify-h{height}")
        self._inflight[height] = task

        def _done(t, h=height):
            if self._inflight.get(h) is t:
                del self._inflight[h]
            # every waiter may have been cancelled (client timeouts
            # are routine on a public proxy) while the shielded task
            # ran on — retrieve the exception so asyncio doesn't log
            # "Task exception was never retrieved" for an error that
            # simply had no one left to deliver to
            if not t.cancelled():
                t.exception()

        task.add_done_callback(_done)
        return await self._await_counted(task)

    async def _await_counted(self, task: asyncio.Task) -> LightBlock:
        """Await the shared verification (shield: a cancelled waiter
        must not cancel the task other coalesced waiters are parked
        on) and count a mid-verification shed PER AFFECTED REQUEST —
        every waiter surfaces a 429, so every waiter moves the shed
        counters, keeping 429s == light_shed_total == /status tally
        even when coalesced joiners ride a verification that sheds."""
        try:
            return await asyncio.shield(task)
        except LightServingShedError:
            self._count_shed(SHED_QUEUE_FULL)
            raise

    def _count_shed(self, reason: str) -> None:
        """ONE shed request: /status tally + metric + the controller
        tracking the pending-verify queue (the collector's, which may
        be an injected test controller — never unconditionally the
        process-global one)."""
        from ..libs.metrics import light_metrics

        self.sheds[reason] += 1
        light_metrics().shed.inc(reason=reason)
        self.collector._controller.shed(PENDING_VERIFY_QUEUE)

    # -- the singleflight body -----------------------------------------

    async def _verify_height(self, height: int,
                             now_ns: int) -> LightBlock:
        rid = next(self._rids)
        self._note("verify", rid, height)
        try:
            lb = await self._verify(rid, height, now_ns)
        except BaseException as e:
            self._note("done", rid, None, type(e).__name__)
            raise
        self._note("done", rid, lb.height(), None)
        return lb

    async def _verify(self, rid: int, height: int,
                      now_ns: int) -> LightBlock:
        cl = self.client
        if not cl._initialized:
            await self.initialize()
        period = cl.trust_options.period_ns
        if height:
            stored = cl.store.get(height)
            if stored is not None:
                if stored.time() + period > now_ns:
                    self.cache.put(stored, now_ns)
                    return stored
                # outside its trusting period: the old verification
                # alone no longer makes it servable (the serial
                # client returns stored blocks unconditionally — the
                # plane serves UNTRUSTED public clients and enforces
                # the cache's documented invariant on the store path
                # too). Below the trusted head the backwards walk
                # re-proves it by hash linkage from an IN-period
                # anchor; at the head there is nothing to anchor on.
                latest = cl.store.latest()
                if latest is None or height >= latest.height():
                    raise OutsideTrustingPeriodError(
                        f"stored header {height} outside trusting "
                        "period")
                return await self._walk_backwards(rid, height, now_ns)
            # the three cases of a height against the trusted store
            # (Client.trusted_base; the cache spares the store's
            # decode of the base)
            trusted = cl.trusted_base(
                height, live=lambda h: self.cache.get(h, now_ns))
            if trusted is None:
                # below the first trusted block: the hash chain down —
                # no commit signatures to batch; the client's walk
                # (with its linkage cache) is already the right tool
                lb = await self._walk_backwards(rid, height, now_ns)
                self.cache.put(lb, now_ns)
                return lb
            target = await cl._from_primary(height)
        else:
            target = await cl._from_primary(0)
            latest = cl.store.latest()
            if latest is not None and \
                    target.height() <= latest.height():
                if latest.time() + period <= now_ns:
                    raise OutsideTrustingPeriodError(
                        f"trusted head {latest.height()} outside "
                        "trusting period")
                self.cache.put(latest, now_ns)
                return latest
            trusted = latest
        # `trusted` was captured BEFORE the fetch (the serial client's
        # order): a concurrent task may have advanced the store past
        # `height` while _from_primary awaited, and a re-read here
        # would make _common_checks refuse a perfectly servable
        # height ("target not above trusted")
        assert trusted is not None
        try:
            await self._verify_skipping(rid, trusted, target, now_ns)
            await cl._detect_divergence(target, now_ns)
        except DivergenceError:
            # a PROVEN fork purged the trusted store above the common
            # height — the LRU may still hold the attacker's chain;
            # drop everything rather than risk serving it
            self.cache.clear()
            raise
        # a mid-verification LightServingShedError propagates
        # UNcounted from here: _await_counted counts it once per
        # affected waiter (the collector raises uncounted too — one
        # request may park two plans and both may shed)
        self.cache.put(target, now_ns)
        return target

    async def _walk_backwards(self, rid: int, height: int,
                              now_ns: int) -> LightBlock:
        lb = await self.client._verify_backwards(height, now_ns)
        self.hash_walks += 1
        self._note("walk", rid, height)
        return lb

    # -- batched skipping verification ---------------------------------

    async def _verify_skipping(self, rid: int, trusted: LightBlock,
                               target: LightBlock,
                               now_ns: int) -> None:
        """Client._verify_skipping with the commit checks routed
        through the coalescing collector: same pivots, same error
        classes, same store writes."""
        cl = self.client
        pending: list[LightBlock] = [target]
        seen: set[int] = {target.height()}
        steps = 0
        while pending:
            steps += 1
            if steps > 200:  # 2^200 heights — unreachable honestly
                raise LightClientError("bisection did not converge")
            block = pending[-1]
            try:
                await self._verify_one(rid, trusted, block, now_ns)
            except NewValSetCantBeTrustedError:
                pivot_h = (trusted.height() + block.height()) // 2
                if pivot_h in (trusted.height(), block.height()) or \
                        pivot_h in seen:
                    raise  # can't split further: genuine failure
                pivot = await cl._from_primary(pivot_h)
                seen.add(pivot_h)
                pending.append(pivot)
                continue
            cl.store.save(block)
            self.cache.put(block, now_ns)
            trusted = block
            pending.pop()

    async def _verify_one(self, rid: int, trusted: LightBlock,
                          untrusted: LightBlock, now_ns: int) -> None:
        """One step (span light.step, one journal entry): _check with
        its outcome recorded. `lanes` are the trusting and the own
        plan's, 0 where a plan was never built."""
        t0 = time.perf_counter_ns()
        gap = untrusted.height() - trusted.height()
        lanes = [0, 0]
        outcome = "ok"
        self.steps += 1
        try:
            await self._check(trusted, untrusted, now_ns, lanes)
        except BaseException as e:
            outcome = type(e).__name__
            raise
        finally:
            self._note("step", rid, trusted.height(), untrusted.height(),
                       lanes[0], lanes[1], outcome)
            tracing.light_leaf(
                tracing.LIGHT_STEP, t0, adjacent=int(gap == 1), gap=gap,
                pivots=int(outcome == "NewValSetCantBeTrustedError"))

    @staticmethod
    def _plan(build, lanes: list, slot: int) -> CommitVerifyPlan:
        """Build one plan (span light.plan); its width into `lanes`."""
        t0 = time.perf_counter_ns()
        plan = None
        try:
            with tracing.TRACER.quiet():   # verify.collect, .sign_batch
                plan = build()
            lanes[slot] = len(plan)
            return plan
        finally:
            tracing.light_leaf(tracing.LIGHT_PLAN, t0,
                               lanes=len(plan) if plan else 0,
                               trusting=int(slot == 0))

    async def _check(self, trusted: LightBlock, untrusted: LightBlock,
                     now_ns: int, lanes: list) -> None:
        """verifier.verify with the signature work pooled: the
        non-crypto checks run inline, the commit check(s) become
        CommitVerifyPlans awaited through the collector — the two
        checks of a non-adjacent step verify CONCURRENTLY, so they
        coalesce with each other and with every other in-flight
        request's checks into the same wide launches."""
        from .verifier import _common_checks

        cl = self.client
        chain_id = cl.chain_id
        period = cl.trust_options.period_ns
        sh = untrusted.signed_header

        def own():
            return untrusted.validator_set.plan_commit_light(
                chain_id, sh.commit.block_id, sh.header.height,
                sh.commit)

        if untrusted.height() == trusted.height() + 1:
            _common_checks(chain_id, trusted, untrusted, period, now_ns)
            if sh.header.validators_hash != \
                    trusted.signed_header.header.next_validators_hash:
                raise VerificationFailedError(
                    "new validators_hash != trusted next_validators_hash")
            try:
                plan = self._plan(own, lanes, 1)
            except VerificationError as e:
                raise VerificationFailedError(
                    f"invalid commit: {e}") from e
            try:
                await self.collector.check(plan)
            except VerificationError as e:
                raise VerificationFailedError(
                    f"invalid commit: {e}") from e
            return
        _common_checks(chain_id, trusted, untrusted, period, now_ns)
        try:
            plan_trusting = self._plan(
                lambda: trusted.validator_set.plan_commit_trusting(
                    chain_id, sh.commit, cl.trust_level.numerator,
                    cl.trust_level.denominator), lanes, 0)
        except VerificationError as e:
            raise NewValSetCantBeTrustedError(str(e)) from e
        try:
            plan_light = self._plan(own, lanes, 1)
        except VerificationError as e:
            # own-commit cannot even reach 2/3: the step is refused as
            # invalid whatever the trusting plan's signatures say, so
            # neither plan is launched
            raise VerificationFailedError(f"invalid commit: {e}") from e
        # both-or-neither admission for the gathered pair: if only
        # ONE slot remains, parking the trusting check and shedding
        # its twin would delay the 429 until the admitted (possibly
        # stalled) launch completes and throw its verdict away —
        # shed promptly instead (the per-check gate in check() stays
        # the hard bound)
        coll = self.collector
        if coll.depth() + 2 > coll.pending_max:
            raise LightServingShedError(coll.depth(), coll.pending_max)
        res_t, res_l = await asyncio.gather(
            self.collector.check(plan_trusting),
            self.collector.check(plan_light),
            return_exceptions=True)
        # error-class parity with verifier.verify_non_adjacent: too
        # little trusted power drove bisection above, at the plan; a
        # signature that does not verify, in either check, is a
        # definitive rejection; anything else (shed, cancellation)
        # propagates untouched
        if isinstance(res_t, VerificationError):
            raise VerificationFailedError(
                f"invalid commit: {res_t}") from res_t
        if isinstance(res_t, BaseException):
            raise res_t
        if isinstance(res_l, VerificationError):
            raise VerificationFailedError(
                f"invalid commit: {res_l}") from res_l
        if isinstance(res_l, BaseException):
            raise res_l

    # -- /status -------------------------------------------------------

    def status_check(self) -> dict:
        """The GET /status `light` check body: backlog fill, request/
        coalesce/cache tallies, shed breakdown, verify-backend split.
        Shedding is designed behavior — only a saturated pending-
        verify backlog degrades the check."""
        from ..crypto import batch as cbatch
        from ..libs.metrics import light_metrics

        met = light_metrics()
        depth = self.collector.depth()
        cap = self.collector.pending_max
        out: dict = {
            "requests": self.requests,
            "coalesced": self.coalesced,
            "cache": {"entries": len(self.cache),
                      "hits": self.cache_hits,
                      "misses": self.cache_misses},
            "queue_depth": depth,
            "queue_capacity": cap,
            "shed": {r: n for r, n in self.sheds.items() if n},
            "steps": self.steps,
            "hash_walks": self.hash_walks,
            "trusted_height": self.client.store.latest_height(),
            "verify_launches": {
                b: int(met.verify_launches.value(backend=b))
                for b in ("device", "host", "host_recheck")
                if met.verify_launches.value(backend=b)},
        }
        fill = depth / cap if cap else 0.0
        if fill >= 0.8:
            out["status"] = "degraded"
            out["detail"] = (f"pending-verify backlog at {fill:.0%}; "
                             "shedding newest requests soon")
        else:
            out["status"] = "ok"
            if not cbatch.device_available("ed25519"):
                out["detail"] = ("ed25519 breaker open: light plane "
                                 "verifying on host")
        return out


class ServingPool:
    """N LightProxy workers sharing ONE plane (one client, one trusted
    store, one verify collector, one cache) — the horizontally
    scalable serving face: more workers add RPC accept/parse
    capacity, while every verification still coalesces in the shared
    plane."""

    def __init__(self, client, workers: int | None = None, config=None,
                 forward_clients=None, proof_runtime=None):
        from ..config import LightConfig
        from .proxy import LightProxy

        cfg = config or LightConfig()
        n = cfg.workers if workers is None else workers
        if n < 1:
            raise ValueError("serving pool needs at least one worker")
        self.plane = ServingPlane(client, cfg)
        fwds = forward_clients or [None] * n
        if len(fwds) != n:
            raise ValueError(
                f"{len(fwds)} forward clients for {n} workers")
        self.proxies = [
            LightProxy(client, forward_client=fwds[i],
                       proof_runtime=proof_runtime, plane=self.plane)
            for i in range(n)
        ]
        self.ports: list[int] = []

    async def listen(self, host: str,
                     ports: list[int] | None = None) -> list[int]:
        ports = ports or [0] * len(self.proxies)
        if len(ports) != len(self.proxies):
            raise ValueError(
                f"{len(ports)} ports for {len(self.proxies)} workers")
        self.ports = [await proxy.listen(host, port)
                      for proxy, port in zip(self.proxies, ports)]
        logger.info("light serving pool: %d workers on %s:%s",
                    len(self.proxies), host, self.ports)
        return self.ports

    async def start(self, host: str, port: int = 0) -> list[int]:
        """What `cmd light` does to serve: load the plane's launch
        shape, pin the trust root through it, THEN accept requests —
        worker i on `port + i` (any free ports for 0)."""
        await self.plane.load_programs()
        await self.plane.initialize()
        return await self.listen(
            host, [port + i if port else 0
                   for i in range(len(self.proxies))])

    def close(self) -> None:
        for proxy in self.proxies:
            proxy.close()
        self.plane.close()
