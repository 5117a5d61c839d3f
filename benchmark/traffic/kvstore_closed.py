"""Traffic: a closed loop of callers over real TCP JSON-RPC against a
one-validator kvstore node that this process runs the way `cmd start`
does (Config -> Node.default_new_node -> start), because only the
chip's owner can take the profiler trace. The callers live in a child
process that never imports JAX (`kvstore_loadgen.py`).

Parameters (the cell's file): `clients`, `signers`, `zipf_s`,
`signed_share`, `bad_share`, `max_in_flight` (the callers' shared pool of connections),
`payload_bytes`, `warm_lanes` (batch sizes that reach the admission
plane's kernel buckets), `height_poll_ms`,
`settle_s`, `readback_sample`.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import socket
import subprocess
import sys
import threading
import time

from benchmark.harness import OUT, REPO, BenchFailure, say


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Driver:
    CONTROLS = ("no_signature_check", "acked_write_lost")

    def __init__(self, run):
        self.run = run
        self.loop = None
        self.node = None
        self.child = None
        self.home = os.path.join(OUT, "node-" + run.cell.name)

    # ------------------------------------------------------- the node

    def _on_loop(self, coro, timeout=None):
        return asyncio.run_coroutine_threadsafe(coro, self.loop).result(
            timeout)

    def setup(self) -> None:
        from tendermint_tpu import cmd
        from tendermint_tpu.config import Config
        from tendermint_tpu.node import Node

        shutil.rmtree(self.home, ignore_errors=True)
        cmd.cmd_init(argparse.Namespace(
            home=self.home, chain_id="bench-kvstore-1"))
        path = os.path.join(self.home, "config", "config.toml")
        cfg = Config.load(path)
        for key, value in self.run.config["config_toml_overrides"].items():
            section, field = key.split(".")
            setattr(getattr(cfg, section), field, value)
        if self.run.rehearse:
            cfg.crypto.backend = "auto"  # no chip to promise
        self.port = _free_port()
        cfg.rpc.laddr = f"tcp://127.0.0.1:{self.port}"
        cfg.p2p.laddr = f"tcp://127.0.0.1:{_free_port()}"
        cfg.save(path)
        cfg = Config.load(path)
        cfg.validate_basic()
        cfg.base.home = self.home
        adm = self.run.config["mempool_admission"]
        got = (cfg.mempool.admission, cfg.mempool.admission_batch,
               cfg.mempool.admission_flush_ms, cfg.mempool.admission_queue)
        if got != (adm["mode"], adm["batch"], adm["flush_ms"], adm["queue"]):
            raise BenchFailure(f"admission defaults moved: {got}")

        self.loop = asyncio.new_event_loop()
        self._thread = threading.Thread(
            target=self.loop.run_forever, name="bench-node", daemon=True)
        self._thread.start()

        async def start():
            self.node = Node.default_new_node(cfg)
            await self.node.start()

        t0 = time.perf_counter()
        self._on_loop(start(), timeout=600)
        while self.node.block_store.height < 2:
            if time.perf_counter() - t0 > 120:
                raise BenchFailure("the node never reached height 2")
            time.sleep(0.1)
        say("node serving", port=self.port,
            boot_s=round(time.perf_counter() - t0, 3))
        self.child = subprocess.Popen(
            [sys.executable,
             os.path.join(REPO, "benchmark", "traffic",
                          "kvstore_loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        self._tell({"port": self.port, "seed": self.run.seed,
                    "params": self.run.params})
        self._hear("ready")

    def _tell(self, obj: dict) -> None:
        self.child.stdin.write(json.dumps(obj) + "\n")
        self.child.stdin.flush()

    def _hear(self, event: str) -> dict:
        line = self.child.stdout.readline()
        if not line:
            raise BenchFailure(
                f"the load generator died (rc={self.child.poll()})")
        msg = json.loads(line)
        if msg.get("event") != event:
            raise BenchFailure(f"load generator said {msg}, not {event}")
        return msg

    def _ask(self, **cmd) -> dict:
        self._tell(cmd)
        return self._hear("done")["out"]

    # ------------------------------------------------------------ run

    def _buckets(self) -> set:
        self.run.ledger.drain()
        return {r["capacity"] for r in self.run.ledger.records
                if r["workload"] == "admission"}

    def warm(self) -> None:
        """The admission plane launches the general kernel at the lane
        bucket of its batch plus a sentinel lane; with at most 200
        requests in flight that is 128 or 256 lanes. Each is reached
        for certain by handing the node's own mempool that many signed
        txs in one tick of its loop (one batch); bursts over RPC then
        go through until one adds no compile."""
        from benchmark.traffic import kvstore_loadgen as lg

        p = self.run.params
        maker = lg.LoadGen(self.port, self.run.seed,
                           dict(p, clients=1, max_in_flight=1))

        async def push(txs):
            return await asyncio.gather(
                *(self.node.mempool.check_tx(tx) for tx in txs))

        for lanes in p["warm_lanes"]:
            txs = [maker.make_tx(2_000_000 + lanes, k, force_signed=True)[1]
                   for k in range(lanes)]
            t0 = time.perf_counter()
            res = self._on_loop(push(txs), timeout=1000)
            say("warm batch", txs=lanes,
                admitted=sum(1 for r in res if r.code == 0),
                buckets=sorted(self._buckets()),
                seconds=round(time.perf_counter() - t0, 3))
        for _ in range(4):
            before = self.run.compiles.count
            out = self._ask(cmd="burst", n=p["clients"])
            say("warm burst", **out, buckets=sorted(self._buckets()),
                compiles=self.run.compiles.count - before)
            if self.run.compiles.count == before:
                break

    def measure(self, seconds: float) -> dict:
        run = self.run
        self._tell({"cmd": "run", "seconds": seconds})
        closed = threading.Event()
        box = {}

        def listen():
            try:
                self._hear("window_closed")
                closed.set()
                box["out"] = self._hear("done")["out"]
            except BenchFailure as e:
                box["error"] = e
            finally:
                closed.set()

        t = threading.Thread(target=listen, daemon=True)
        t.start()
        while not closed.wait(0.2):
            run.ledger.drain()   # the ring holds 512 launches
        run.ledger.drain()
        t.join(timeout=120)
        if "out" not in box:
            raise box.get("error") or BenchFailure(
                "the load generator never reported its window")
        out = box["out"]
        run.samples["checktx_ms"] = out.pop("checktx_ms")
        run.counters["signed_txs_attempted"] = out["signed_txs_attempted"]
        say("window", **out)
        for need in ("sigs_per_s", "tx_commit_p95_ms"):
            if out.get(need) is None:
                raise BenchFailure(f"the window gave no {need}: {out}")
        return {"attempted": out["attempted"], "failed": out["failed"],
                "metrics": {"sigs_per_s": out["sigs_per_s"],
                            "tx_commit_p95_ms": out["tx_commit_p95_ms"]}}

    def check(self, control: str | None = None) -> dict:
        out = self._ask(cmd="check", control=control)
        return {k: (tuple(v) if isinstance(v, list) else v)
                for k, v in out.items()}

    def close(self) -> None:
        if self.child is not None and self.child.poll() is None:
            try:
                self._tell({"cmd": "quit"})
                self.child.wait(timeout=20)
            except (OSError, subprocess.TimeoutExpired):
                self.child.kill()
                self.child.wait()
        if self.node is not None:
            try:
                self._on_loop(self.node.stop(), timeout=60)
            except Exception as e:  # the result is already decided
                say("node stop failed", error=repr(e))
        if self.loop is not None:
            async def cancel_rest():
                rest = [t for t in asyncio.all_tasks()
                        if t is not asyncio.current_task()]
                for t in rest:
                    t.cancel()
                await asyncio.gather(*rest, return_exceptions=True)

            self._on_loop(cancel_rest(), timeout=30)
            self.loop.call_soon_threadsafe(self.loop.stop)
            self._thread.join(timeout=10)
            self.loop.close()
