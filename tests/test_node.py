"""Node assembly: full default node from a home directory — produces
blocks, accepts txs, restarts from disk, and forms a 2-node net via
persistent peers (reference: node/node_test.go)."""

import asyncio
import os

from tendermint_tpu.config import Config, fast_consensus_config
from tendermint_tpu.node import Node
from tendermint_tpu.privval import FilePV
from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator

from helpers import GENESIS_TIME


def run(coro):
    return asyncio.run(coro)


def make_home(tmp_path, name, gdoc, fast_sync=False):
    home = str(tmp_path / name)
    os.makedirs(os.path.join(home, "config"), exist_ok=True)
    os.makedirs(os.path.join(home, "data"), exist_ok=True)
    cfg = Config()
    cfg.base.home = home
    cfg.base.moniker = name
    cfg.base.fast_sync = fast_sync
    cfg.consensus = fast_consensus_config()
    cfg.consensus.wal_file = "data/cs.wal/wal"
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    gdoc.save(os.path.join(home, "config", "genesis.json"))
    return cfg


def single_val_genesis(n=1):
    pvs = [FilePV.generate() for _ in range(n)]
    gdoc = GenesisDoc(
        chain_id="node-test-chain",
        genesis_time=GENESIS_TIME,
        validators=[GenesisValidator(pv.get_pub_key(), 10) for pv in pvs],
    )
    gdoc.validate_and_complete()
    return gdoc, pvs


def test_single_node_produces_blocks_and_accepts_txs(tmp_path):
    async def go():
        gdoc, pvs = single_val_genesis()
        cfg = make_home(tmp_path, "n0", gdoc)
        pv = pvs[0]
        pv.key_path = cfg.base.resolve(cfg.base.priv_validator_key_file)
        pv.state_path = cfg.base.resolve(cfg.base.priv_validator_state_file)
        pv.save_key()

        node = Node.default_new_node(cfg)
        await node.start()
        try:
            await node.consensus_state.wait_for_height(3, timeout=60)
            # a tx through the mempool lands in a block and the app
            res = await node.mempool.check_tx(b"hello=world")
            assert res.code == 0
            for _ in range(200):
                if node.client_creator.app.size > 0:
                    break
                await asyncio.sleep(0.05)
            assert node.client_creator.app.size == 1
        finally:
            await node.stop()

        # restart from the same home: WAL + stores recover
        node2 = Node.default_new_node(cfg)
        await node2.start()
        try:
            h = node2.state.last_block_height
            assert h >= 3
            await node2.consensus_state.wait_for_height(h + 2, timeout=60)
            assert node2.client_creator.app.size == 1  # tx survived restart
        finally:
            await node2.stop()

    run(go())


def test_two_node_net_via_persistent_peers(tmp_path):
    async def go():
        gdoc, pvs = single_val_genesis(2)
        cfg0 = make_home(tmp_path, "p0", gdoc)
        cfg1 = make_home(tmp_path, "p1", gdoc)
        nodes = []
        for cfg, pv in ((cfg0, pvs[0]), (cfg1, pvs[1])):
            pv.key_path = cfg.base.resolve(cfg.base.priv_validator_key_file)
            pv.state_path = cfg.base.resolve(
                cfg.base.priv_validator_state_file)
            pv.save_key()
            nodes.append(Node.default_new_node(cfg))
        await nodes[0].start()
        try:
            cfg1.p2p.persistent_peers = nodes[0].p2p_addr
            await nodes[1].start()
            try:
                await asyncio.gather(
                    *(n.consensus_state.wait_for_height(3, timeout=60)
                      for n in nodes))
                assert all(n.switch.n_peers() == 1 for n in nodes)
            finally:
                await nodes[1].stop()
        finally:
            await nodes[0].stop()

    run(go())


def test_trust_metric_wired_into_live_node(tmp_path):
    """The behaviour reporter isn't vapor: a real 2-node net credits
    VERIFIED votes into each node's trust store (via the consensus
    batch path), and stopping persists the history to trust.db."""
    async def go():
        gdoc, pvs = single_val_genesis(2)
        cfgs = [make_home(tmp_path, f"tn{i}", gdoc) for i in range(2)]
        nodes = []
        for i, cfg in enumerate(cfgs):
            pv = pvs[i]
            pv.key_path = cfg.base.resolve(cfg.base.priv_validator_key_file)
            pv.state_path = cfg.base.resolve(
                cfg.base.priv_validator_state_file)
            pv.save_key()
            nodes.append(Node.default_new_node(cfg))
        await nodes[0].start()
        await nodes[1].start()
        try:
            await nodes[1].switch.dial_peer(nodes[0].p2p_addr)
            await asyncio.gather(
                *(n.consensus_state.wait_for_height(3, timeout=60)
                  for n in nodes))
            for n in nodes:
                rep = n.switch.reporter
                assert rep is not None and rep.trust.size() >= 1
                peer_id, metric = next(iter(rep.trust.metrics.items()))
                assert metric.good > 0 or metric.num_intervals > 0
                assert metric.trust_score() > 50
        finally:
            for n in nodes:
                await n.stop()
        data_dir = os.path.join(cfgs[0].base.home, "data")
        trust_db = next(
            (os.path.join(data_dir, f) for f in os.listdir(data_dir)
             if f in ("trust.sqlite", "trust.db")), None)
        assert trust_db is not None
        # persisted history survives reopen, whatever the backend
        from tendermint_tpu.libs.db import FileDB, SqliteDB

        store = SqliteDB(trust_db) if trust_db.endswith(".sqlite") \
            else FileDB(trust_db)
        assert any(k.startswith(b"trusthistory")
                   for k, _ in store.iterate())
        store.close()

    run(go())


def test_null_tx_indexer_disables_search(tmp_path):
    """tx_index.indexer = "null" (reference config.go TxIndexConfig):
    the node runs without indexers and the search RPCs error."""

    async def go():
        gdoc, pvs = single_val_genesis()
        cfg = make_home(tmp_path, "nullidx", gdoc)
        cfg.tx_index.indexer = "null"
        pv = pvs[0]
        pv.key_path = cfg.base.resolve(cfg.base.priv_validator_key_file)
        pv.state_path = cfg.base.resolve(cfg.base.priv_validator_state_file)
        pv.save_key()

        from tendermint_tpu.rpc.core import RPCError

        node = Node.default_new_node(cfg)
        await node.start()
        try:
            assert node.indexer_service is None
            await node.consensus_state.wait_for_height(2, timeout=60)
            env = node.rpc_env()
            for coro in (env.tx(None, hash="ab" * 32),
                         env.tx_search(None, query="tx.height=1"),
                         env.block_search(None, query="block.height=1")):
                try:
                    await coro
                    raise AssertionError("expected RPCError")
                except RPCError as e:
                    assert "disabled" in str(e.message)
        finally:
            await node.stop()

    run(go())


def test_remote_signer_node(tmp_path):
    """priv_validator_laddr (reference node.go:663): a node with NO
    local key listens for a remote signer; a sidecar dials in with the
    validator key and the solo-validator net produces blocks — only
    possible if every proposal+vote round-trips through the signer."""

    async def go():
        import socket

        from tendermint_tpu.crypto.ed25519 import Ed25519PrivKey
        from tendermint_tpu.privval.signer import SignerServer

        gdoc, pvs = single_val_genesis()
        cfg = make_home(tmp_path, "rsig", gdoc)
        # validator key lives ONLY in the signer, not the node home
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        cfg.base.priv_validator_laddr = f"tcp://127.0.0.1:{port}"

        # SecretConnection both ways (the node keys on its node key)
        signer = SignerServer(pvs[0], gdoc.chain_id,
                              conn_key=Ed25519PrivKey.generate())

        async def dial_and_serve():
            for _ in range(200):
                try:
                    reader, writer = await asyncio.open_connection(
                        "127.0.0.1", port)
                    break
                except OSError:
                    await asyncio.sleep(0.05)
            else:
                raise AssertionError("node never listened for signer")
            await signer.serve_connection(reader, writer)

        loop = asyncio.get_running_loop()
        sidecar = loop.create_task(dial_and_serve())
        node = Node.default_new_node(cfg)
        assert node.priv_validator is None  # no local key loaded
        await node.start()
        try:
            from tendermint_tpu.privval.signer import SignerClient

            assert isinstance(node.priv_validator, SignerClient)
            await node.consensus_state.wait_for_height(3, timeout=60)
            # Link drop + signer redial: the validator must resume
            # signing on the replacement connection, not go mute.
            node.priv_validator._drop_link()
            sidecar.cancel()
            sidecar2 = loop.create_task(dial_and_serve())
            h = node.consensus_state.rs.height
            await node.consensus_state.wait_for_height(h + 2,
                                                       timeout=60)
            sidecar2.cancel()
        finally:
            await node.stop()
            sidecar.cancel()

    run(go())


def test_crypto_backend_tpu_is_binding_and_auto_starts(tmp_path):
    """`[crypto] backend = "tpu"` refuses to start on a backend that
    is not a TPU (the tests' CPU mesh) with an error that says why;
    `auto` on the same home starts and commits."""
    import pytest

    async def go():
        gdoc, pvs = single_val_genesis()
        cfg = make_home(tmp_path, "n0", gdoc)
        pv = pvs[0]
        pv.key_path = cfg.base.resolve(cfg.base.priv_validator_key_file)
        pv.state_path = cfg.base.resolve(cfg.base.priv_validator_state_file)
        pv.save_key()

        cfg.crypto.backend = "tpu"
        node = Node.default_new_node(cfg)
        with pytest.raises(RuntimeError) as e:
            await node.start()
        msg = str(e.value)
        assert 'backend = "tpu"' in msg and "'cpu'" in msg
        assert "refusing to start" in msg

        cfg.crypto.backend = "auto"
        node = Node.default_new_node(cfg)
        await node.start()
        try:
            await node.consensus_state.wait_for_height(2, timeout=60)
        finally:
            await node.stop()

    run(go())
