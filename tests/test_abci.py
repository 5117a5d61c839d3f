"""ABCI: codec round-trips, local + socket transports, kvstore apps,
AppConns multiplexer."""

import asyncio

from tendermint_tpu.abci import types as t
from tendermint_tpu.abci.client import ClientCreator, LocalClient
from tendermint_tpu.abci.kvstore import (
    KVStoreApp, PersistentKVStoreApp, encode_validator_tx,
)
from tendermint_tpu.abci.server import SocketServer
from tendermint_tpu.libs.db import MemDB
from tendermint_tpu.proxy import AppConns


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def test_codec_roundtrip():
    msgs = [
        t.RequestEcho("hello"),
        t.RequestInfo("v1", 11, 8),
        t.RequestDeliverTx(b"\x00\xffbinary"),
        t.RequestBeginBlock(
            hash=b"\x01" * 32,
            header={"height": 5},
            last_commit_info=t.LastCommitInfo(
                round=1, votes=[t.VoteInfo(b"\xaa" * 20, 10, True)]
            ),
        ),
        t.ResponseCheckTx(code=3, log="bad", gas_wanted=7),
        t.ResponseEndBlock(
            validator_updates=[t.ValidatorUpdate("ed25519", b"\x02" * 32, 5)]
        ),
        t.ResponseListSnapshots([t.Snapshot(9, 1, 3, b"h" * 32, b"meta")]),
        t.RequestOfferSnapshot(t.Snapshot(9, 1, 3, b"h" * 32), b"a" * 32),
    ]
    for m in msgs:
        assert t.decode_msg(t.encode_msg(m)) == m


def test_kvstore_app_flow():
    async def go():
        app = KVStoreApp()
        client = LocalClient(app)
        await client.start()
        info = await client.info(t.RequestInfo())
        assert info.last_block_height == 0
        r = await client.deliver_tx(t.RequestDeliverTx(b"name=satoshi"))
        assert r.is_ok()
        c = await client.commit()
        assert c.data != b""
        q = await client.query(t.RequestQuery(data=b"name"))
        assert q.value == b"satoshi"
        q2 = await client.query(t.RequestQuery(data=b"missing"))
        assert q2.value == b""
        info2 = await client.info(t.RequestInfo())
        assert info2.last_block_height == 1
        await client.stop()

    run(go())


def test_persistent_kvstore_restart_and_validators():
    async def go():
        db = MemDB()
        app = PersistentKVStoreApp(db)
        client = LocalClient(app)
        await client.start()
        pk = b"\x07" * 32
        r = await client.deliver_tx(
            t.RequestDeliverTx(encode_validator_tx(pk.hex(), 42))
        )
        assert r.is_ok()
        eb = await client.end_block(t.RequestEndBlock(1))
        assert eb.validator_updates == [t.ValidatorUpdate("ed25519", pk, 42)]
        await client.commit()
        q = await client.query(t.RequestQuery(data=pk.hex().encode(), path="/val"))
        assert q.value == b"42"
        await client.stop()

        # restart from the same db: height + validators survive
        app2 = PersistentKVStoreApp(db)
        client2 = LocalClient(app2)
        await client2.start()
        info = await client2.info(t.RequestInfo())
        assert info.last_block_height == 1
        q = await client2.query(t.RequestQuery(data=pk.hex().encode(), path="/val"))
        assert q.value == b"42"
        await client2.stop()

    run(go())


def test_persistent_kvstore_snapshots():
    async def go():
        app = PersistentKVStoreApp()
        c = LocalClient(app)
        await c.start()
        for i in range(5):
            await c.deliver_tx(t.RequestDeliverTx(b"k%d=v%d" % (i, i)))
        await c.commit()
        snaps = (await c.list_snapshots()).snapshots
        assert len(snaps) == 1 and snaps[0].height == 1

        # restore into a fresh app
        app2 = PersistentKVStoreApp()
        c2 = LocalClient(app2)
        await c2.start()
        offer = await c2.offer_snapshot(
            t.RequestOfferSnapshot(snaps[0], app.app_hash)
        )
        assert offer.result == t.OfferSnapshotResult.ACCEPT
        for i in range(snaps[0].chunks):
            chunk = (await c.load_snapshot_chunk(
                t.RequestLoadSnapshotChunk(snaps[0].height, 1, i)
            )).chunk
            r = await c2.apply_snapshot_chunk(
                t.RequestApplySnapshotChunk(i, chunk)
            )
            assert r.result == t.ApplySnapshotChunkResult.ACCEPT
        assert app2.app_hash == app.app_hash
        assert app2.db.get(b"kv:k3") == b"v3"
        await c.stop()
        await c2.stop()

    run(go())


def test_socket_transport_pipelined():
    async def go():
        app = KVStoreApp()
        server = SocketServer(app, port=0)
        await server.start()
        from tendermint_tpu.abci.client import SocketClient

        client = SocketClient("127.0.0.1", server.port)
        await client.start()
        echo = await client.echo("ping")
        assert echo.message == "ping"
        # pipeline 50 DeliverTxs without awaiting each
        tasks = [
            client.submit(t.RequestDeliverTx(b"k%d=v%d" % (i, i)))
            for i in range(50)
        ]
        results = await asyncio.gather(*tasks)
        assert all(r.is_ok() for r in results)
        await client.flush()
        c = await client.commit()
        assert c.data != b""
        q = await client.query(t.RequestQuery(data=b"k17"))
        assert q.value == b"v17"
        await client.stop()
        await server.stop()

    run(go())


def test_socket_server_survives_app_exception():
    class BadApp(t.Application):
        def deliver_tx(self, req):
            raise RuntimeError("boom")

    async def go():
        server = SocketServer(BadApp(), port=0)
        await server.start()
        from tendermint_tpu.abci.client import ABCIClientError, SocketClient

        client = SocketClient("127.0.0.1", server.port)
        await client.start()
        try:
            await client.deliver_tx(t.RequestDeliverTx(b"x"))
            raise AssertionError("expected ABCIClientError")
        except ABCIClientError:
            pass
        # connection still alive for the next request
        echo = await client.echo("still-here")
        assert echo.message == "still-here"
        await client.stop()
        await server.stop()

    run(go())


def test_app_conns_share_one_app():
    async def go():
        app = KVStoreApp()
        conns = AppConns(ClientCreator(app=app))
        await conns.start()
        await conns.consensus.deliver_tx(t.RequestDeliverTx(b"a=1"))
        await conns.consensus.commit()
        q = await conns.query.query(t.RequestQuery(data=b"a"))
        assert q.value == b"1"
        ct = await conns.mempool.check_tx(t.RequestCheckTx(b"b=2"))
        assert ct.is_ok()
        await conns.stop()

    run(go())


def test_half_delivered_block_replay_is_idempotent():
    """A node dying mid-block leaves the (external, still-running) app
    with half-delivered txs; the handshake then replays the SAME block
    from BeginBlock. The staged-overlay design must discard the
    partial writes instead of double-applying (found by randomized
    campaign seed 131: restarted node diverged with wrong AppHash —
    app hash counted a tx twice)."""
    import struct

    from tendermint_tpu.abci import types as t
    from tendermint_tpu.abci.kvstore import (
        PersistentKVStoreApp, encode_validator_tx,
    )

    app = PersistentKVStoreApp()
    # block 1, fully committed
    app.begin_block(t.RequestBeginBlock())
    app.deliver_tx(t.RequestDeliverTx(b"a=1"))
    app.deliver_tx(t.RequestDeliverTx(b"b=2"))
    app.end_block(t.RequestEndBlock(1))
    app.commit(t.RequestCommit())
    assert app.size == 2 and app.height == 1

    # block 2: half-delivered (kv tx + validator tx), then the node
    # dies — no EndBlock/Commit
    app.begin_block(t.RequestBeginBlock())
    app.deliver_tx(t.RequestDeliverTx(b"c=3"))
    app.deliver_tx(t.RequestDeliverTx(
        encode_validator_tx("11" * 32, 5)))
    # writes are LIVE mid-block (reference kvstore behavior, goldens
    # depend on it) but journaled
    assert app.size == 3 and app.db.get(b"kv:c") == b"3"
    assert app.validators["11" * 32] == 5

    # restarted node's handshake replays block 2 from scratch —
    # BeginBlock must first roll the half-applied writes back
    app.begin_block(t.RequestBeginBlock())
    app.deliver_tx(t.RequestDeliverTx(b"c=3"))
    app.deliver_tx(t.RequestDeliverTx(
        encode_validator_tx("11" * 32, 5)))
    eb = app.end_block(t.RequestEndBlock(2))
    res = app.commit(t.RequestCommit())
    # exactly once: size 3 (not 4), validator present once
    assert app.size == 3
    assert res.data == struct.pack(">Q", 3)
    assert app.validators["11" * 32] == 5
    assert len(eb.validator_updates) == 1
    assert app.db.get(b"kv:c") == b"3"


def test_statesync_restore_clears_stale_journal():
    """A snapshot restore on an app holding a half-delivered block's
    journal must NOT replay that journal into the restored state
    (review finding on the journal design)."""
    from tendermint_tpu.abci import types as t
    from tendermint_tpu.abci.kvstore import PersistentKVStoreApp

    src = PersistentKVStoreApp(snapshot_interval=1)
    src.begin_block(t.RequestBeginBlock())
    src.deliver_tx(t.RequestDeliverTx(b"x=1"))
    src.end_block(t.RequestEndBlock(1))
    src.commit(t.RequestCommit())
    snaps = src.list_snapshots(t.RequestListSnapshots()).snapshots
    assert snaps

    dst = PersistentKVStoreApp()
    # dst has a half-delivered block in flight when it restores
    dst.begin_block(t.RequestBeginBlock())
    dst.deliver_tx(t.RequestDeliverTx(b"stale=9"))
    snap = snaps[-1]
    dst.offer_snapshot(t.RequestOfferSnapshot(snapshot=snap,
                                              app_hash=src.app_hash))
    for i in range(snap.chunks):
        chunk = src.load_snapshot_chunk(
            t.RequestLoadSnapshotChunk(
                height=snap.height, format=snap.format, chunk=i)).chunk
        dst.apply_snapshot_chunk(t.RequestApplySnapshotChunk(
            index=i, chunk=chunk))
    # next block begins: the stale journal must not roll anything back
    dst.begin_block(t.RequestBeginBlock())
    assert dst.size == src.size == 1
    assert dst.db.get(b"kv:x") == b"1"
    res = dst.commit(t.RequestCommit())
    assert res.data == src.app_hash


def _deliver_block(app, txs):
    app.begin_block(t.RequestBeginBlock())
    for tx in txs:
        app.deliver_tx(t.RequestDeliverTx(tx))
    app.end_block(t.RequestEndBlock(app.height + 1))


def test_block_is_one_durable_commit(tmp_path):
    """N DeliverTx + Commit on a SqliteDB: nothing is written before
    Commit, and Commit is ONE durable commit (a `db.write`) of the N
    keys and the app's state record — also on a height that takes and
    prunes a snapshot."""
    from tendermint_tpu.libs.db import SqliteDB
    from tendermint_tpu.libs.tracing import DB_WRITE, TRACER

    def commits():
        return [r for r in TRACER.snapshot() if r[0] == DB_WRITE]

    db = SqliteDB(str(tmp_path / "app.sqlite"))
    app = PersistentKVStoreApp(db, snapshot_interval=2, keep_snapshots=1)
    was, TRACER.enabled = TRACER.enabled, True
    try:
        for h in range(1, 5):
            TRACER.clear()
            _deliver_block(app, [b"k%d-%d=v" % (h, i) for i in range(50)])
            assert not commits() and db.get(b"kv:k%d-0" % h) is None
            app.commit(t.RequestCommit())
            (w,) = commits()
            # 50 keys + the state record; a snapshot height adds its
            # snapshot, and from the second one the pruned one's delete
            assert w[6].get("n", 1) == 1
            assert w[6]["ops"] == 51 + (h % 2 == 0) + (h == 4)
            assert db.get(b"kv:k%d-49" % h) == b"v"
    finally:
        TRACER.enabled = was
        TRACER.clear()
    assert [k for k, _ in db.iterate_prefix(b"snap:")] == \
        [b"snap:%016x" % 4]
    db.close()


def test_crash_before_commit_leaves_whole_blocks_only(tmp_path):
    """A process that dies between DeliverTx and Commit: the reopened
    db holds none of that block's keys under the old state record, and
    replaying the block gives the app hash and size of a clean run."""
    from tendermint_tpu.abci.kvstore import _STATE_KEY
    from tendermint_tpu.libs.db import SqliteDB

    block1 = [b"a=1", b"b=2"]
    block2 = [b"a=9", b"c=3", encode_validator_tx("11" * 32, 5)]

    clean = PersistentKVStoreApp()
    for txs in (block1, block2):
        _deliver_block(clean, txs)
        clean.commit(t.RequestCommit())

    path = str(tmp_path / "app.sqlite")
    app = PersistentKVStoreApp(SqliteDB(path))
    _deliver_block(app, block1)
    app.commit(t.RequestCommit())
    state1 = app.db.get(_STATE_KEY)
    _deliver_block(app, block2)          # ... and the process dies
    app.db.close()

    db = SqliteDB(path)
    assert db.get(b"kv:c") is None and db.get(b"kv:a") == b"1"
    assert db.get(_STATE_KEY) == state1
    app = PersistentKVStoreApp(db)
    assert (app.height, app.size) == (1, 2)
    assert "11" * 32 not in app.validators
    # the handshake replays block 2 from BeginBlock
    _deliver_block(app, block2)
    res = app.commit(t.RequestCommit())
    assert res.data == clean.app_hash
    assert (app.height, app.size) == (clean.height, clean.size) == (2, 4)
    assert app.validators == clean.validators
    assert db.get(b"kv:a") == b"9" and db.get(b"kv:c") == b"3"
    db.close()


def test_query_sees_the_staged_block():
    """Reads of the live state go through the overlay: a query
    mid-block answers with the staged value (the abci-cli goldens'
    behaviour), a dropped block's value is gone again."""
    app = PersistentKVStoreApp()
    _deliver_block(app, [b"k=old"])
    app.commit(t.RequestCommit())
    _deliver_block(app, [b"k=new", b"fresh=1"])
    assert app.query(t.RequestQuery(data=b"k")).value == b"new"
    assert app.query(t.RequestQuery(data=b"fresh")).log == "exists"
    assert app.db.base.get(b"kv:k") == b"old"
    assert dict(app.db.iterate_prefix(b"kv:")) == \
        {b"kv:fresh": b"1", b"kv:k": b"new"}
    app.begin_block(t.RequestBeginBlock())      # the block never commits
    assert app.query(t.RequestQuery(data=b"k")).value == b"old"
    assert app.query(t.RequestQuery(data=b"fresh")).log == "does not exist"
    assert app.size == 1


def test_block_overlay_iterates_like_the_landed_db():
    """The overlay's merged view (staged sets, overwrites and deletes
    over the base, in range) equals the base's own after landing."""
    from tendermint_tpu.abci.kvstore import BlockOverlay

    base = MemDB()
    for k in (b"a", b"c", b"e", b"g"):
        base.set(k, b"base-" + k)
    ov = BlockOverlay(base)
    ov.set(b"0", b"first")
    ov.set(b"c", b"over")
    ov.set(b"d", b"between")
    ov.delete(b"e")
    ov.delete(b"never-there")
    ov.set(b"z", b"last")
    ranges = [(b"", None), (b"b", b"f"), (b"c", b"d"), (b"h", None)]
    before = [list(ov.iterate(lo, hi)) for lo, hi in ranges]
    assert before[0] == [(b"0", b"first"), (b"a", b"base-a"),
                         (b"c", b"over"), (b"d", b"between"),
                         (b"g", b"base-g"), (b"z", b"last")]
    assert ov.get(b"e") is None and not ov.has(b"e") and base.has(b"e")
    ov.write_batch([(b"y", b"with-the-batch")])
    assert base.get(b"y") == b"with-the-batch" and base.get(b"e") is None
    assert [[kv for kv in base.iterate(lo, hi) if kv[0] != b"y"]
            for lo, hi in ranges] == before


def test_merkle_app_hash_and_proof_cover_the_staged_block():
    """MerkleKVStoreApp hashes and proves at Commit THROUGH the
    overlay: root and value proof equal those computed straight from
    the pairs, as the unstaged app computed them from its db."""
    from tendermint_tpu.abci import kv_proofs
    from tendermint_tpu.abci.kvstore import MerkleKVStoreApp
    from tendermint_tpu.crypto import merkle

    app = MerkleKVStoreApp(MemDB())
    pairs = {}
    for txs in ([b"m=1", b"a=2"], [b"m=3", b"z=4", b"b=5"]):
        _deliver_block(app, txs)
        # a proof mid-block still answers from the last commit
        stale = app.query(t.RequestQuery(data=b"m", prove=True))
        assert stale.value == pairs.get(b"m", b"")
        res = app.commit(t.RequestCommit())
        pairs.update(tx.split(b"=") for tx in txs)
        want = sorted(pairs.items())
        root, proofs = merkle.proofs_from_byte_slices(
            [kv_proofs.kv_leaf(k, v) for k, v in want])
        assert res.data == app.app_hash == root
        resp = app.query(t.RequestQuery(data=b"m", prove=True))
        assert resp.value == pairs[b"m"]
        assert resp.proof_ops == [kv_proofs.KVValueOp.encode(
            b"m", len(want), proofs[[k for k, _ in want].index(b"m")])]
        assert kv_proofs.kv_proof_runtime().verify_value(
            [merkle.ProofOp(o["type"], o["key"], o["data"])
             for o in resp.proof_ops], root, [b"m"], pairs[b"m"])
