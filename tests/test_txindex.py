"""Unit tests for the tx and block event indexers
(reference: state/txindex/kv/kv_test.go; BlockIndexer matches the
released v0.34.x state/indexer/block/kv semantics)."""

import pytest

from tendermint_tpu.libs.db import MemDB
from tendermint_tpu.libs.pubsub import Query
from tendermint_tpu.state.txindex import BlockIndexer, TxIndexer, TxResult


def _tx(height, index, tx, events):
    return TxResult(height, index, tx, {"code": 0, "events": events})


def _ev(etype, **attrs):
    return {"type": etype,
            "attributes": [{"key": k, "value": v}
                           for k, v in attrs.items()]}


def test_tx_search_equality_and_ranges():
    ix = TxIndexer(MemDB())
    ix.index(_tx(1, 0, b"a", [_ev("transfer", amount="100")]))
    ix.index(_tx(2, 0, b"b", [_ev("transfer", amount="250")]))
    ix.index(_tx(2, 1, b"c", [_ev("mint", amount="100")]))

    got = ix.search(Query.parse("transfer.amount = '100'"))
    assert [t.tx for t in got] == [b"a"]
    # unquoted numeric literal must match the string-stored attribute
    got = ix.search(Query.parse("transfer.amount = 100"))
    assert [t.tx for t in got] == [b"a"]
    got = ix.search(Query.parse("tx.height = 2"))
    assert [t.tx for t in got] == [b"b", b"c"]
    got = ix.search(Query.parse("tx.height > 1"))
    assert [t.tx for t in got] == [b"b", b"c"]


def test_tx_search_slash_value_not_prefix_matched():
    ix = TxIndexer(MemDB())
    ix.index(_tx(1, 0, b"plain", [_ev("app", path="5")]))
    ix.index(_tx(2, 0, b"slashy", [_ev("app", path="5/x")]))
    got = ix.search(Query.parse("app.path = '5'"))
    assert [t.tx for t in got] == [b"plain"]
    got = ix.search(Query.parse("app.path = '5/x'"))
    assert [t.tx for t in got] == [b"slashy"]


def test_block_indexer_search():
    bi = BlockIndexer(MemDB())
    bi.index(1, {"events": [_ev("rewards", amount="10")]}, {})
    bi.index(2, {}, {"events": [_ev("rewards", amount="100")]})
    bi.index(3, {"events": [_ev("slash", val="v1")]}, {})

    assert bi.search(Query.parse("block.height = 2")) == [2]
    assert bi.search(Query.parse("block.height >= 2")) == [2, 3]
    # unquoted number matches the string-stored value, not "100.0"
    assert bi.search(Query.parse("rewards.amount = 100")) == [2]
    assert bi.search(Query.parse("slash.val = 'v1'")) == [3]
    assert bi.search(Query.parse("rewards.amount > 50")) == [2]
    assert bi.search(Query.parse("rewards.amount <= 50")) == [1]


def test_block_indexer_exists_and_slash_values():
    bi = BlockIndexer(MemDB())
    bi.index(1, {"events": [_ev("app", denom="atom")]}, {})
    bi.index(2, {"events": [_ev("app", denom="atom/chan-0")]}, {})

    # EXISTS on a never-emitted event matches nothing (not everything)
    assert bi.search(Query.parse("ghost.key EXISTS")) == []
    assert bi.search(Query.parse("app.denom EXISTS")) == [1, 2]
    # a value extending the queried one past '/' is not a match
    assert bi.search(Query.parse("app.denom = 'atom'")) == [1]
    assert bi.search(Query.parse("app.denom = 'atom/chan-0'")) == [2]


def test_height_literal_edge_cases():
    bi = BlockIndexer(MemDB())
    bi.index(3, {"events": [_ev("e", k="v")]}, {})
    # fractional height matches nothing (no truncation to 3)
    assert bi.search(Query.parse("block.height = 3.5")) == []
    # non-numeric height matches nothing instead of raising
    assert bi.search(Query.parse("block.height = 'abc'")) == []
    ix = TxIndexer(MemDB())
    ix.index(_tx(3, 0, b"t", []))
    assert ix.search(Query.parse("tx.height = 3.5")) == []
    assert ix.search(Query.parse("tx.height = 'abc'")) == []
    assert [t.tx for t in ix.search(Query.parse("tx.height = 3"))] == [b"t"]


class _CountingDB(MemDB):
    def __init__(self):
        super().__init__()
        self.batches = 0

    def write_batch(self, ops) -> None:
        self.batches += 1
        super().write_batch(ops)


_BLOCK = [
    _tx(7, 0, b"a=1", [_ev("app", creator="kvstore", key="a")]),
    _tx(7, 1, b"b=2", [_ev("app", creator="kvstore", key="b"),
                       _ev("transfer", amount="5/x")]),
    _tx(7, 2, b"c=3", []),
    _tx(8, 0, b"d=4", [_ev("app", creator="kvstore", key="d")]),
]


def _index_each(ix):
    for tr in _BLOCK:
        ix.index(tr)
    return len(_BLOCK)


def _index_batch(ix):
    ix.index_batch(_BLOCK)
    return 1


@pytest.mark.parametrize("form", [_index_each, _index_batch])
def test_batch_form_indexes_what_single_calls_index(form):
    """index() is index_batch()'s one-element case: both forms leave
    the same keys and values, answer get / tx_search alike, and the
    batch form is ONE write_batch for the whole block."""
    db = _CountingDB()
    ix = TxIndexer(db)
    assert form(ix) == db.batches

    ref = MemDB()
    for tr in _BLOCK:   # the index as N single writes lay it down
        TxIndexer(ref).index(tr)
    assert list(db.iterate()) == list(ref.iterate())

    for tr in _BLOCK:
        assert ix.get(tr.hash()) == tr
    assert ix.get(b"\x00" * 32) is None

    def search(q):
        return [t.tx for t in ix.search(Query.parse(q))]

    assert search("tx.height = 7") == [b"a=1", b"b=2", b"c=3"]
    assert search("tx.height = 8") == [b"d=4"]
    assert search("app.key = 'b'") == [b"b=2"]
    assert search("app.creator = 'kvstore'") == [b"a=1", b"b=2", b"d=4"]
    assert search("app.creator = 'kvstore' AND tx.height = 7") == \
        [b"a=1", b"b=2"]
    assert search("transfer.amount = '5/x'") == [b"b=2"]
    assert search("transfer.amount = '5'") == []


@pytest.mark.parametrize("blocks", [[3], [0, 2], [2, 3]])
def test_indexer_service_writes_one_batch_a_block(blocks):
    """The Tx events a block fires in one synchronous run reach the db
    as one write_batch; an empty block writes nothing."""
    import asyncio

    from tendermint_tpu.state.txindex import IndexerService
    from tendermint_tpu.types.events import EventBus, EventDataTx

    async def go():
        db = _CountingDB()
        ix = TxIndexer(db)
        bus = EventBus()
        svc = IndexerService(ix, bus)
        svc.start()
        want = 0
        for h, n in enumerate(blocks, start=1):
            for i in range(n):    # as BlockExecutor._fire_events does
                ev = _ev("app", key=f"k{h}-{i}")
                bus.publish_tx(
                    EventDataTx(h, b"k%d-%d=v" % (h, i), i,
                                {"code": 0, "log": "", "events": [ev]}),
                    [ev])
            want += 1 if n else 0
            for _ in range(50):   # the indexer's task takes its turn
                if db.batches == want:
                    break
                await asyncio.sleep(0.01)
            assert db.batches == want
            got = ix.search(Query.parse(f"tx.height = {h}"))
            assert [(t.index, t.tx) for t in got] == \
                [(i, b"k%d-%d=v" % (h, i)) for i in range(n)]
            for i in range(n):
                assert [t.index for t in ix.search(
                    Query.parse(f"app.key = 'k{h}-{i}'"))] == [i]
        svc.stop()
        # Subscription.next() leaves its queue getter behind when its
        # task is cancelled: unwind it before the loop closes
        rest = asyncio.all_tasks() - {asyncio.current_task()}
        for task in rest:
            task.cancel()
        await asyncio.gather(*rest, return_exceptions=True)

    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(go())
    finally:
        loop.close()
