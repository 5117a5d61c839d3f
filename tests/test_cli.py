"""CLI: init/testnet/key tooling round-trips and a started node
reachable over RPC (reference: cmd/tendermint tests)."""

import asyncio
import json
import os
import subprocess
import sys
import time

from tendermint_tpu.cmd import main


def test_init_and_key_commands(tmp_path, capsys):
    home = str(tmp_path / "home")
    assert main(["--home", home, "init", "--chain-id", "cli-chain"]) == 0
    for rel in ("config/genesis.json", "config/node_key.json",
                "config/priv_validator_key.json", "config/config.toml"):
        assert os.path.exists(os.path.join(home, rel)), rel
    # idempotent
    assert main(["--home", home, "init"]) == 0

    capsys.readouterr()
    assert main(["--home", home, "show-node-id"]) == 0
    node_id = capsys.readouterr().out.strip()
    assert len(node_id) == 40

    assert main(["--home", home, "show-validator"]) == 0
    v = json.loads(capsys.readouterr().out)
    assert v["type"] == "ed25519" and len(bytes.fromhex(v["value"])) == 32

    assert main(["--home", home, "gen-validator"]) == 0
    g = json.loads(capsys.readouterr().out)
    assert len(bytes.fromhex(g["address"])) == 20

    assert main(["--home", home, "version"]) == 0
    assert "tendermint-tpu" in capsys.readouterr().out

    # reset wipes data but keeps keys
    data_marker = os.path.join(home, "data", "blockstore.db")
    open(data_marker, "w").close()
    assert main(["--home", home, "unsafe-reset-all"]) == 0
    assert not os.path.exists(data_marker)
    assert os.path.exists(os.path.join(home, "config/node_key.json"))


def test_testnet_generates_mesh(tmp_path):
    out = str(tmp_path / "net")
    assert main(["testnet", "--v", "3", "--o", out,
                 "--chain-id", "mesh-chain",
                 "--starting-port", "29000"]) == 0
    genesis_hashes = set()
    for i in range(3):
        home = os.path.join(out, f"node{i}")
        gen = json.load(open(os.path.join(home, "config/genesis.json")))
        assert len(gen["validators"]) == 3
        genesis_hashes.add(json.dumps(gen, sort_keys=True))
        cfg = open(os.path.join(home, "config/config.toml")).read()
        assert f"tcp://127.0.0.1:{29000 + i}" in cfg
        assert cfg.count("@127.0.0.1:") == 2  # peers with the other two
    assert len(genesis_hashes) == 1  # identical genesis everywhere


def test_cli_start_serves_rpc(tmp_path):
    """Boot `python -m tendermint_tpu.cmd start` as a real subprocess
    and hit its RPC — the closest thing to a user's first experience."""
    home = str(tmp_path / "home")
    assert main(["--home", home, "init", "--chain-id", "boot-chain"]) == 0
    # single node: no peers to fast-sync from
    cfg_path = os.path.join(home, "config", "config.toml")
    cfg = open(cfg_path).read()
    cfg = cfg.replace('laddr = "tcp://127.0.0.1:26657"',
                      'laddr = "tcp://127.0.0.1:28757"')
    cfg = cfg.replace('laddr = "tcp://0.0.0.0:26656"',
                      'laddr = "tcp://127.0.0.1:28756"')
    cfg = cfg.replace("fast_sync = true", "fast_sync = false")
    cfg = cfg.replace("timeout_commit_ms = 1000", "timeout_commit_ms = 50")
    open(cfg_path, "w").write(cfg)

    env = dict(os.environ)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "tendermint_tpu.cmd", "--home", home,
         "start"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env)
    try:
        from tendermint_tpu.rpc.jsonrpc import HTTPClient

        async def probe():
            cli = HTTPClient("127.0.0.1", 28757, timeout=5)
            deadline = time.monotonic() + 60
            while True:
                try:
                    st = await cli.call("status")
                    if int(st["sync_info"]["latest_block_height"]) >= 2:
                        return st
                except Exception:
                    if time.monotonic() > deadline:
                        raise
                await asyncio.sleep(0.5)

        st = asyncio.run(probe())
        assert st["node_info"]["network"] == "boot-chain"
    finally:
        proc.terminate()
        try:
            proc.wait(10)
        except subprocess.TimeoutExpired:
            proc.kill()


def test_light_command_once(tmp_path, capsys):
    """`light --once` verifies the head of a running node over RPC."""
    from p2p_harness import P2PNode
    from helpers import make_genesis
    from tendermint_tpu.node import Node  # noqa: F401 (import check)

    async def go():
        # past genesis: the CLI light client uses the wall clock, so
        # headers must not look like they're from the future
        from helpers import deterministic_pv
        from tendermint_tpu.types.genesis import (
            GenesisDoc, GenesisValidator,
        )

        pvs = [deterministic_pv(0)]
        gdoc = GenesisDoc(chain_id="light-cli-chain",
                          genesis_time=time.time_ns() - 60 * 10**9,
                          validators=[GenesisValidator(
                              pvs[0].get_pub_key(), 10)])
        gdoc.validate_and_complete()
        a = P2PNode(gdoc, pvs[0], "full")
        await a.start()
        try:
            await a.cs.wait_for_height(4, timeout=60)
            # expose a's stores over RPC by attaching an Environment
            from tendermint_tpu.rpc.core import Environment, serve

            class _Shim:
                pass

            shim = _Shim()
            shim.block_store = a.block_store
            shim.state_store = a.state_store
            shim.state = a.cs.state
            shim.node_key = a.node_key
            shim.genesis_doc = a.gdoc
            from tendermint_tpu.config import RPCConfig

            shim.config = type("C", (), {"base": type(
                "B", (), {"moniker": "shim"})(), "rpc": RPCConfig()})()
            shim.consensus_state = a.cs
            shim.bc_reactor = a.bc_reactor
            shim.priv_validator = None
            shim.switch = a.switch
            shim.listen_addr = ""
            shim.mempool = a.cs.mempool
            shim.tx_indexer = None
            shim.evpool = a.evpool
            shim.event_bus = None
            shim.proxy_app = a.conns
            srv, port = await serve(Environment(shim), "127.0.0.1", 0)
            try:
                trusted_hash = \
                    a.block_store.load_block_meta(1).block_id.hash.hex()

                import threading

                rc = {}

                def run_light():
                    rc["code"] = main([
                        "light", gdoc.chain_id,
                        "--primary", f"127.0.0.1:{port}",
                        "--trust-height", "1",
                        "--trust-hash", trusted_hash,
                        "--once",
                    ])

                t = threading.Thread(target=run_light)
                t.start()
                for _ in range(300):
                    if not t.is_alive():
                        break
                    await asyncio.sleep(0.1)
                assert not t.is_alive(), "light client did not finish"
                assert rc["code"] == 0
            finally:
                srv.close()
        finally:
            await a.stop()

    asyncio.run(go())


def test_unsafe_reset_priv_validator(tmp_path, capsys):
    """reference reset_priv_validator.go: wipes ONLY the last-sign
    state; key file survives (or is regenerated when absent); data
    stays intact."""
    home = str(tmp_path / "home")
    assert main(["--home", home, "init"]) == 0
    key_file = os.path.join(home, "config/priv_validator_key.json")
    state_file = os.path.join(home, "data/priv_validator_state.json")
    key_before = open(key_file).read()
    os.makedirs(os.path.dirname(state_file), exist_ok=True)
    with open(state_file, "w") as f:
        json.dump({"height": 7, "round": 1, "step": 3}, f)
    data_marker = os.path.join(home, "data", "blockstore.db")
    open(data_marker, "w").close()

    assert main(["--home", home, "unsafe-reset-priv-validator"]) == 0
    assert not os.path.exists(state_file), "last-sign state must be wiped"
    assert open(key_file).read() == key_before, "key must survive"
    assert os.path.exists(data_marker), "data must stay intact"

    os.remove(key_file)
    assert main(["--home", home, "unsafe-reset-priv-validator"]) == 0
    assert os.path.exists(key_file), "missing key must be regenerated"


def test_unsafe_reset_all_addrbook_flag(tmp_path, capsys):
    home = str(tmp_path / "home")
    assert main(["--home", home, "init"]) == 0
    book = os.path.join(home, "config", "addrbook.json")
    with open(book, "w") as f:
        f.write("{}")
    assert main(["--home", home, "unsafe-reset-all",
                 "--keep-addr-book"]) == 0
    assert os.path.exists(book), "--keep-addr-book must preserve it"
    assert main(["--home", home, "unsafe-reset-all"]) == 0
    assert not os.path.exists(book), "default reset removes the addrbook"


def test_replay_console_steps_and_quits(tmp_path, capsys, monkeypatch):
    """replay-console decodes the rotated WAL read-only and steps on
    input; 'q' exits early, missing WAL is a clean error."""
    from tendermint_tpu.consensus import wal as walmod

    home = str(tmp_path / "home")
    assert main(["--home", home, "init"]) == 0
    assert main(["--home", home, "replay-console"]) == 1  # no WAL yet

    wal_path = os.path.join(home, "data", "cs.wal", "wal")
    w = walmod.WAL(wal_path)
    for h in (1, 2):
        w.write(walmod.EndHeightMessage(h), time_ns=h * 1000)
    w.flush_and_sync()
    w.close()

    feeds = iter(["", "q"])  # step one, then quit
    monkeypatch.setattr("builtins.input", lambda *_: next(feeds))
    capsys.readouterr()
    # read-only: must work with the WAL files write-protected
    os.chmod(wal_path, 0o444)
    try:
        assert main(["--home", home, "replay-console"]) == 0
    finally:
        os.chmod(wal_path, 0o644)
    out = capsys.readouterr().out
    assert "1 segment(s)" in out
    assert "EndHeightMessage" in out
