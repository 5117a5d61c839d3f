"""The load generator of `light_serve`: a process of its own that never
imports JAX or the program. It speaks JSON-RPC over TCP to the light
proxy's ports and answers its parent's commands, one JSON object a line
on stdin, one a line on stdout:

    {"cmd": "run", "seconds": 30}     the closed loop for that long
    {"cmd": "run", "requests": 300}   ... or for that many requests
    {"cmd": "ports", "ports": [..]}   a fresh proxy took the next replay
    {"cmd": "quit"}

and says {"event": "replay_done"} when a replay's cursor has passed the
chain's top and every reply is in (the parent then closes the proxy,
opens a fresh one and answers with its ports), {"event":
"window_closed"} when the time is up, and {"event": "done", "out": ...}
with every reply of the run.

Traffic: `clients` callers in a closed loop (each sends its next
request when its reply arrives) over `connections` pooled keep-alive
connections. Request k of a replay is, by (seed, replay, k) alone, one
time in `latest_one_in` a `commit` with no height (the newest header)
and otherwise `commit?height=h` with h from ONE ascending cursor all
callers share, 2 -> the chain's top (the scan). A caller whose request
is refused (a forged first answer of the primary) asks once more.
"""

from __future__ import annotations

import asyncio
import hashlib
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.traffic import light_chain  # noqa: E402


class Conn:
    """One keep-alive HTTP connection speaking JSON-RPC."""

    def __init__(self, port: int):
        self.port = port
        self.reader = self.writer = None
        self._id = 0

    async def call(self, method: str, **params):
        if self.writer is None:
            self.reader, self.writer = await asyncio.open_connection(
                "127.0.0.1", self.port)
        self._id += 1
        body = json.dumps({"jsonrpc": "2.0", "id": self._id,
                           "method": method, "params": params}).encode()
        self.writer.write(
            b"POST / HTTP/1.1\r\nHost: rpc\r\nContent-Type: "
            b"application/json\r\nContent-Length: "
            + str(len(body)).encode() + b"\r\n\r\n" + body)
        await self.writer.drain()
        length = 0
        while True:
            line = await self.reader.readline()
            if not line:
                raise ConnectionError("the proxy closed the connection")
            if line in (b"\r\n", b"\n"):
                break
            if line.lower().startswith(b"content-length:"):
                length = int(line.split(b":", 1)[1])
        return json.loads(await self.reader.readexactly(length))

    def close(self) -> None:
        if self.writer is not None:
            self.writer.close()
            self.writer = None


class LoadGen:
    def __init__(self, hello: dict):
        self.seed, self.p = hello["seed"], hello["params"]
        self.top = self.p["heights"]
        self.hashes = hello["hashes"]     # height -> the chain's hash
        self.replay = hello.get("replay", 0)
        self.replies: list[dict] = []
        self.pool: asyncio.Queue = asyncio.Queue()
        self._conns: list[Conn] = []
        self.set_ports(hello["ports"])

    def set_ports(self, ports: list[int]) -> None:
        for c in self._conns:
            c.close()
        self.pool = asyncio.Queue()
        self._conns = [Conn(ports[i % len(ports)])
                       for i in range(self.p["connections"])]
        for c in self._conns:
            self.pool.put_nowait(c)

    async def ask(self, height: int, again: bool) -> dict:
        """One `commit` through the pool; `t0` when the caller had it
        ready, `t1` when its reply was read."""
        rec = {"r": self.replay, "h": height, "again": again,
               "t0": time.monotonic()}
        conn = await self.pool.get()
        try:
            r = await conn.call(
                "commit", **({"height": str(height)} if height else {}))
        except (OSError, asyncio.IncompleteReadError, ValueError) as e:
            conn.close()
            r = {"error": {"message": repr(e)}}
        finally:
            self.pool.put_nowait(conn)
        rec["t1"] = time.monotonic()
        if "result" in r:
            served = int(r["result"]["signed_header"]["header"]["height"])
            rec["served"] = served
            rec["right"] = (
                served == (height or self.top)
                and light_chain.reply_hash(r["result"]).hex()
                == self.hashes.get(str(served)))
        else:
            rec["error"] = str(r.get("error", {}).get("message"))[:160]
        self.replies.append(rec)
        return rec

    async def _caller(self, state: dict) -> None:
        while not state["stop"]():
            k = state["k"]
            state["k"] += 1
            u = hashlib.sha256(
                f"bench/light/{self.seed}/{self.replay}/{k}".encode()
            ).digest()
            if int.from_bytes(u[:8], "little") % self.p["latest_one_in"]:
                if state["cursor"] > self.top:
                    return
                height = state["cursor"]
                state["cursor"] += 1
            else:
                height = 0
            rec = await self.ask(height, False)
            if "error" in rec:
                await self.ask(height, True)

    async def one_replay(self, stop) -> bool:
        """True when the replay ran to its end."""
        state = {"k": 0, "cursor": 2, "stop": stop}
        await asyncio.gather(*(self._caller(state)
                               for _ in range(self.p["clients"])))
        return state["cursor"] > self.top

    async def run(self, seconds, requests, next_ports) -> dict:
        t0 = time.monotonic()
        n0 = len(self.replies)
        if seconds is not None:
            def stop():
                return time.monotonic() >= t0 + seconds
        else:
            def stop():
                return len(self.replies) - n0 >= requests
        while not stop():
            if await self.one_replay(stop):
                print(json.dumps({"event": "replay_done",
                                  "replay": self.replay}), flush=True)
                self.replay += 1
                self.set_ports(await next_ports())
        t1 = time.monotonic()
        print(json.dumps({"event": "window_closed"}), flush=True)
        replies, self.replies = self.replies[n0:], self.replies[:n0]
        return {"t0": t0, "t1": t1, "replies": replies,
                "replay": self.replay}


async def serve() -> None:
    loop = asyncio.get_running_loop()

    async def hear() -> dict:
        line = await loop.run_in_executor(None, sys.stdin.readline)
        return json.loads(line) if line else {"cmd": "quit"}

    lg = LoadGen(await hear())
    print(json.dumps({"event": "ready"}), flush=True)

    async def next_ports() -> list[int]:
        cmd = await hear()
        if cmd.get("cmd") != "ports":
            raise RuntimeError(f"expected the next proxy's ports: {cmd}")
        return cmd["ports"]

    while True:
        cmd = await hear()
        if cmd["cmd"] == "quit":
            return
        if cmd["cmd"] == "run":
            out = await lg.run(cmd.get("seconds"), cmd.get("requests"),
                               next_ports)
        elif cmd["cmd"] == "ports":
            lg.replay = cmd["replay"]
            lg.set_ports(cmd["ports"])
            out = {}
        else:
            out = {"error": f"unknown command {cmd['cmd']!r}"}
        print(json.dumps({"event": "done", "cmd": cmd["cmd"], "out": out}),
              flush=True)


if __name__ == "__main__":
    assert "jax" not in sys.modules
    asyncio.run(serve())
    assert "jax" not in sys.modules, "the load generator imported jax"
