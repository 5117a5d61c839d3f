"""Size-or-deadline micro-batch collector: the one flusher under the
verify planes (mempool/admission.py, light/serving.py), which differ
only in what an item is and what a batch of them costs to verify.

``submit(item, weight)`` parks the item and awaits its result. A
single flusher task opens a ``flush_ms`` deadline at the first pending
arrival, cuts a batch at ``batch_max`` of weight or at the deadline
(whichever first; a cut holds at most ``batch_max`` of weight, and an
item heavier than that goes alone) and runs it through the plane's
verify callable in an executor thread — so a slow device (or an armed
delay failpoint) backs up the bounded backlog and sheds instead of
stalling the event loop. One batch is in flight at a time.

The backlog bound counts ITEMS (parked + in verify): at the bound the
newest arrival is refused with BacklogFull, which each plane turns
into its own shed error and accounting. The backlog is a tracked
bounded queue of the libs/overload.py QUEUES catalog under the
plane's queue name.

Each batch leaves one pair of root spans, of the kinds the plane
hands in: a queue wait (first pending arrival -> the cut; attrs
``lanes``, ``cut`` = full|deadline, ``wait_sum_ms``) and a flush (the
cut -> last result delivered; ``lanes``, and whatever the verify
callable sets on it — both planes set ``backend``). The flush span is
handed to the worker thread, so the crypto.verify of a device launch
is its child.
"""

from __future__ import annotations

import asyncio
import collections
import logging
import time
from typing import NamedTuple

from ..libs.overload import CONTROLLER
from ..libs.tracing import TRACER

logger = logging.getLogger("crypto.collector")


class BacklogFull(Exception):
    """The backlog is at its bound: the newest arrival is refused —
    transient backpressure, NOT a verdict on the item."""

    def __init__(self, depth: int, limit: int):
        super().__init__(f"backlog full: {depth} pending (limit {limit})")
        self.depth = depth
        self.limit = limit


class _Parked(NamedTuple):
    item: object
    weight: int
    future: asyncio.Future
    t_ns: int       # enqueue perf_counter_ns: the queue-wait span's stamp


class BatchCollector:
    """The collector. `run_batch(items) -> results` (one result an
    item, in order) is called in an executor thread, once a batch;
    `span_kinds` is the (queue wait, flush) pair of registered span
    kinds; `queue` the QUEUES catalog name the backlog is tracked
    under; `limit` the backlog bound in items."""

    def __init__(self, *, queue: str, limit: int, batch_max: int,
                 flush_ms: float, run_batch, span_kinds: tuple[str, str],
                 controller=None):
        self.batch_max = max(1, batch_max)
        self.flush_ms = flush_ms
        self.limit = max(1, limit)
        self._queue = queue
        self._run_batch = run_batch
        self._wait_kind, self._flush_kind = span_kinds
        self._controller = controller or CONTROLLER
        self._pending: collections.deque[_Parked] = collections.deque()
        # maintained incrementally: submit() and the flusher consult it
        # per enqueue/wakeup, and a scan of a deep backlog there would
        # make admission quadratic exactly under load
        self._pending_weight = 0
        self._in_flight = 0
        self._item_evt = asyncio.Event()   # set on every enqueue
        self._full_evt = asyncio.Event()   # set when batch_max reached
        self._flusher: asyncio.Task | None = None
        self._controller.register(queue, self.depth, lambda: self.limit,
                                  owner=self)

    # -- sizes ---------------------------------------------------------

    def depth(self) -> int:
        """Backlog the bound applies to: parked + currently verifying."""
        return len(self._pending) + self._in_flight

    def saturated(self) -> bool:
        return self.depth() >= self.limit

    # -- lifecycle -----------------------------------------------------

    def close(self) -> None:
        if self._flusher is not None:
            self._flusher.cancel()
            self._flusher = None
        for p in self._pending:
            if not p.future.done():
                p.future.cancel()
        self._pending.clear()
        self._pending_weight = 0
        self._controller.unregister(self._queue, owner=self)

    # -- the await-a-result entry point --------------------------------

    async def submit(self, item, weight: int = 1):
        """Queue `item` for the next batch; returns its result. Raises
        BacklogFull (shed-newest) when the backlog is at its bound."""
        if self.depth() >= self.limit:
            raise BacklogFull(self.depth(), self.limit)
        loop = asyncio.get_running_loop()
        if self._flusher is None or self._flusher.done():
            self._flusher = loop.create_task(
                self._flush_loop(), name=f"{self._queue}-flusher")
        fut = loop.create_future()
        self._pending.append(
            _Parked(item, weight, fut, time.perf_counter_ns()))
        self._pending_weight += weight
        self._item_evt.set()
        if self._pending_weight >= self.batch_max:
            self._full_evt.set()
        return await fut

    # -- flusher -------------------------------------------------------

    async def _flush_loop(self) -> None:
        # The flusher outlives the request whose arrival started it:
        # detach from that request's span so that each batch's
        # queue-wait / flush pair is a root of its own.
        with TRACER.attach(None):
            await self._flush_batches()

    async def _flush_batches(self) -> None:
        loop = asyncio.get_running_loop()
        while True:
            while not self._pending:
                self._item_evt.clear()
                await self._item_evt.wait()
            # first item arrived: hold the batch open until the deadline
            # or until it fills, whichever comes first. The deadline is
            # on the loop's clock (virtual under sim/); only the span
            # stamps are perf_counter_ns.
            deadline = loop.time() + self.flush_ms / 1000.0
            while self._pending_weight < self.batch_max:
                remaining = deadline - loop.time()
                if remaining <= 0:
                    break
                self._full_evt.clear()
                try:
                    await asyncio.wait_for(self._full_evt.wait(),
                                           remaining)
                except asyncio.TimeoutError:
                    break
            full = self._pending_weight >= self.batch_max
            # a cut never outweighs batch_max (a plane that launches a
            # closed set of shapes counts on it); an item heavier than
            # batch_max still goes, alone
            batch: list[_Parked] = []
            lanes = 0
            while self._pending and (
                    not batch or lanes + self._pending[0].weight
                    <= self.batch_max):
                batch.append(self._pending.popleft())
                lanes += batch[-1].weight
            self._pending_weight -= lanes
            self._in_flight = len(batch)
            cut = time.perf_counter_ns()
            TRACER.begin(
                self._wait_kind, start_ns=batch[0].t_ns, lanes=lanes,
                cut="full" if full else "deadline",
                wait_sum_ms=sum(cut - p.t_ns for p in batch) / 1e6,
            ).end()
            try:
                # the span goes to the worker thread by hand
                # (TRACER.wrap): crypto.verify is then its child
                with TRACER.span(self._flush_kind, lanes=lanes):
                    results = await loop.run_in_executor(
                        None, TRACER.wrap(self._run_batch),
                        [p.item for p in batch])
                    for p, res in zip(batch, results):
                        if not p.future.done():
                            p.future.set_result(res)
            except asyncio.CancelledError:
                for p in batch:
                    if not p.future.done():
                        p.future.cancel()
                raise
            except Exception as e:  # defensive: a result must always land
                logger.exception("%s verify batch died", self._queue)
                for p in batch:
                    if not p.future.done():
                        p.future.set_exception(e)
            finally:
                self._in_flight = 0
