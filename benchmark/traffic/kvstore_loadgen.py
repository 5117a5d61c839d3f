"""The load generator of `kvstore_closed`: a process of its own that
never imports JAX or the program. It speaks JSON-RPC over TCP to the
node, signs with OpenSSL, and answers its parent's commands, one JSON
object a line on stdin, one a line on stdout:

    {"cmd": "burst", "n": 200}        n signed txs all in flight at once
    {"cmd": "run", "seconds": 30}     the closed loop; then waits until
                                      what it sent has committed
    {"cmd": "check", "control": null} read-back and model replay
    {"cmd": "quit"}

Traffic: `clients` callers in a closed loop on the COMMIT: each sends
`broadcast_tx_sync`, waits for the CheckTx reply and then, as a wallet
with one account sequence does, for the block that holds its tx before
it sends its next (a refused tx is followed at once). So load never
passes what the node commits, and arrives as it does from such callers:
in a burst after every block. Tx k of client c depends
on (seed, c, k) alone: signed by one of `signers` keys drawn with
Zipf(`zipf_s`) popularity (`signed_share`), signed with one bit of the
signature flipped (`bad_share`), or an unsigned `k=v` (the rest);
payloads are `payload_bytes` long and never repeat.
"""

from __future__ import annotations

import asyncio
import base64
import bisect
import hashlib
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import gen  # noqa: E402
from benchmark.reference.kvstore_model import KVStoreModel  # noqa: E402

# the signed-tx envelope (types/tx_envelope.py), written out again here
MAGIC = b"\xf5\x54\x58\x01"
SIGN_DOMAIN = b"tendermint-tpu/tx-envelope/v1\x00"
CODE_ADMISSION_REJECT = 429


def _lv(field: int, b: bytes) -> bytes:
    assert len(b) < 128
    return bytes([(field << 3) | 2, len(b)]) + b


def envelope(pub: bytes, sig: bytes, payload: bytes) -> bytes:
    return MAGIC + _lv(1, pub) + _lv(2, sig) + _lv(3, payload)


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
                raise ConnectionError("the node closed the connection")
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
    def __init__(self, port: int, seed: int, p: dict):
        self.port, self.seed, self.p = port, seed, p
        self.keys = gen.private_keys(seed, "signer", p["signers"])
        self.pubs = [gen.public_bytes(k) for k in self.keys]
        weights = [1.0 / (r + 1) ** p["zipf_s"]
                   for r in range(p["signers"])]
        total = sum(weights)
        acc, self.cdf = 0.0, []
        for w in weights:
            acc += w / total
            self.cdf.append(acc)
        self.sent: list[dict] = []     # every tx of the window
        self.warmed = 0                # txs sent by warm-up bursts
        self.seen_at: dict[int, float] = {}
        self.blocks: dict[int, list[bytes]] = {}
        self.waiting: dict[bytes, asyncio.Future] = {}
        self._counter = [0] * p["clients"]
        self.window = None
        # the callers share a gateway's pool of connections, kept under
        # the node's default limit of 256 requests in flight
        self.pool: asyncio.Queue = asyncio.Queue()
        for _ in range(p["max_in_flight"]):
            self.pool.put_nowait(Conn(port))

    # ---------------------------------------------------------- traffic

    def signed(self, client: int, k: int):
        """(public key, payload, signature) of client's k-th tx."""
        h = hashlib.sha256(
            f"bench/tx/{self.seed}/{client}/{k}".encode()).digest()
        v = int.from_bytes(h[8:16], "little") / 2.0 ** 64
        fill = hashlib.sha256(h).hexdigest().encode() * 4
        payload = (f"b{self.seed:x}c{client:x}k{k:x}/".encode()
                   + fill)[:self.p["payload_bytes"]]
        signer = min(bisect.bisect_left(self.cdf, v), len(self.keys) - 1)
        return (self.pubs[signer], payload,
                self.keys[signer].sign(SIGN_DOMAIN + payload))

    def make_tx(self, client: int, k: int, force_signed=False):
        """(kind, tx bytes) of client's k-th tx."""
        h = hashlib.sha256(
            f"bench/tx/{self.seed}/{client}/{k}".encode()).digest()
        u = int.from_bytes(h[:8], "little") / 2.0 ** 64
        p = self.p
        if not force_signed and u >= p["signed_share"] + p["bad_share"]:
            fill = hashlib.sha256(h).hexdigest().encode() * 4
            tx = (f"b{self.seed:x}c{client:x}k{k:x}=".encode()
                  + fill)[:p["payload_bytes"]]
            return "unsigned", tx
        pub, payload, sig = self.signed(client, k)
        kind = "signed"
        if not force_signed and u >= p["signed_share"]:
            kind = "bad"
            sig = sig[:7] + bytes([sig[7] ^ 1]) + sig[8:]
        return kind, envelope(pub, sig, payload)

    async def ask(self, conn: Conn, method: str, **params) -> dict:
        """A control call (status, block, query): retried while the
        node answers 429 busy."""
        for _ in range(200):
            r = await conn.call(method, **params)
            if "result" in r:
                return r["result"]
            if r.get("error", {}).get("code") != CODE_ADMISSION_REJECT:
                raise RuntimeError(f"{method}: {r}")
            await asyncio.sleep(0.05)
        raise RuntimeError(f"{method}: the node stayed busy")

    async def send(self, tx: bytes) -> dict:
        """One tx through the pool: `t0` when the caller had it ready,
        `ts` when a connection was free and it went out."""
        rec = {"tx": tx, "t0": time.monotonic()}
        conn = await self.pool.get()
        rec["ts"] = time.monotonic()
        try:
            r = await conn.call("broadcast_tx_sync",
                                tx=base64.b64encode(tx).decode())
        except (OSError, asyncio.IncompleteReadError, ValueError) as e:
            conn.close()
            rec["error"] = repr(e)
            r = {}
        finally:
            self.pool.put_nowait(conn)
        rec["t1"] = time.monotonic()
        if "error" in r:  # shed by a full queue (429, "retry later")
            rec["busy"] = r["error"].get("code") == CODE_ADMISSION_REJECT
            rec["code"] = CODE_ADMISSION_REJECT if rec["busy"] else -1
        elif "result" in r:
            rec["code"] = r["result"]["code"]
        return rec

    async def burst(self, n: int) -> dict:
        """Warm-up: n signed txs handed to the pool at once."""
        txs = [self.make_tx(1_000_000 + self.warmed + i, 0,
                            force_signed=True)[1] for i in range(n)]
        self.warmed += n
        t0 = time.monotonic()
        recs = await asyncio.gather(*(self.send(tx) for tx in txs))
        return {"sent": n, "ok": sum(1 for r in recs if r.get("code") == 0),
                "seconds": time.monotonic() - t0}

    async def _client(self, c: int, t_end: float) -> None:
        while time.monotonic() < t_end:
            k = self._counter[c]
            self._counter[c] += 1
            kind, tx = self.make_tx(c, k)
            held = asyncio.get_running_loop().create_future()
            self.waiting[tx] = held
            rec = await self.send(tx)
            rec["kind"] = kind
            self.sent.append(rec)
            if rec.get("code") == 0:
                await held          # the block that holds it
            else:
                self.waiting.pop(tx, None)
                if rec.get("busy"):
                    await asyncio.sleep(0.05)

    async def _watch_chain(self, conn: Conn, stop: asyncio.Event) -> None:
        """Polls the height; a new block is fetched once, its commit is
        timed at the poll that first showed it, and the callers whose
        txs it holds are released."""
        last = None
        while not stop.is_set():
            try:
                r = await self.ask(conn, "status")
                h = int(r["sync_info"]["latest_block_height"])
                now = time.monotonic()
                if last is None:
                    last = h
                for hh in range(last + 1, h + 1):
                    self.seen_at[hh] = now
                    b = await self.ask(conn, "block", height=str(hh))
                    txs = [base64.b64decode(t)
                           for t in b["block"]["data"]["txs"] or []]
                    self.blocks[hh] = txs
                    for tx in txs:
                        held = self.waiting.pop(tx, None)
                        if held is not None and not held.done():
                            held.set_result(hh)
                last = max(last, h)
            except (OSError, asyncio.IncompleteReadError):
                conn.close()
            await asyncio.sleep(self.p["height_poll_ms"] / 1e3)

    async def run(self, seconds: float) -> dict:
        stop = asyncio.Event()
        watcher = asyncio.ensure_future(
            self._watch_chain(Conn(self.port), stop))
        await asyncio.sleep(0.1)
        t0 = time.monotonic()
        callers = [asyncio.ensure_future(self._client(c, t0 + seconds))
                   for c in range(self.p["clients"])]
        await asyncio.sleep(seconds)
        t1 = time.monotonic()
        self.window = (t0, t1)
        print(json.dumps({"event": "window_closed"}), flush=True)
        # what the window sent commits within the next heights
        done, late = await asyncio.wait(callers,
                                        timeout=self.p["settle_s"])
        for task in late:
            task.cancel()
        await asyncio.gather(*callers, return_exceptions=True)
        stop.set()
        await watcher
        query = Conn(self.port)
        await self._fetch_blocks(query)
        query.close()
        return self._metrics(len(late))

    async def _fetch_blocks(self, conn: Conn) -> None:
        """The blocks the watcher did not see (before the window)."""
        r = await self.ask(conn, "status")
        top = int(r["sync_info"]["latest_block_height"])
        for h in range(1, top + 1):
            if h not in self.blocks:
                b = await self.ask(conn, "block", height=str(h))
                self.blocks[h] = [base64.b64decode(t)
                                  for t in b["block"]["data"]["txs"] or []]
        self.where = {tx: h for h, txs in self.blocks.items() for tx in txs}

    def _metrics(self, never_committed: int) -> dict:
        t0, t1 = self.window
        seen_at = self.seen_at
        inside = sorted(h for h, t in seen_at.items() if t0 <= t <= t1)
        signed = {r["tx"] for r in self.sent if r["kind"] == "signed"}
        out = {"blocks_in_window": len(inside)}
        if len(inside) >= 2:
            first, last = inside[0], inside[-1]
            n = sum(1 for h in range(first + 1, last + 1)
                    for tx in self.blocks.get(h, []) if tx in signed)
            out["sigs_per_s"] = n / (seen_at[last] - seen_at[first])
            out["signed_committed_whole_blocks"] = n
        lat, missing = [], 0
        for r in self.sent:
            if r["kind"] == "bad":
                continue
            h = self.where.get(r["tx"])
            if r.get("code") != 0 or h is None or h not in seen_at:
                missing += 1
            else:
                lat.append((seen_at[h] - r["t0"]) * 1e3)
        lat.sort()
        # a tx refused, shed or lost missed every limit: it sits at
        # the far end of the distribution
        n_all = len(lat) + missing
        rank = min(n_all - 1, int(0.95 * n_all))
        out["tx_commit_p95_ms"] = lat[rank] if rank < len(lat) else None
        out["tx_commit_p50_ms"] = lat[len(lat) // 2] if lat else None
        bad = [r for r in self.sent if r["kind"] == "bad"]
        out.update(
            attempted=len(self.sent),
            failed=missing + sum(
                1 for r in bad if r.get("code") != CODE_ADMISSION_REJECT),
            missing=missing,
            signed_txs_attempted=sum(
                1 for r in self.sent if r["kind"] != "unsigned"),
            kinds={k: sum(1 for r in self.sent if r["kind"] == k)
                   for k in ("signed", "bad", "unsigned")},
            codes={str(c): sum(1 for r in self.sent if r.get("code") == c)
                   for c in {r.get("code") for r in self.sent}},
            checktx_ms=[(r["t1"] - r["ts"]) * 1e3 for r in self.sent],
            callers_still_waiting=never_committed,
            block_interval_s=((seen_at[inside[-1]] - seen_at[inside[0]])
                              / (len(inside) - 1)) if len(inside) > 1
            else None,
            window_s=t1 - t0,
        )
        return out

    # ------------------------------------------------------------ check

    async def check(self, control: str | None) -> dict:
        """The guarantees, as far as a run can show them. `control`
        puts a weakened node in the program's place (README.md)."""
        bad = [r for r in self.sent if r["kind"] == "bad"]
        codes = {r["tx"]: r.get("code") for r in bad}
        if control == "no_signature_check":
            # what CheckTx says when nothing verifies the envelope:
            # the kvstore admits every tx
            codes = {tx: 0 for tx in codes}
        model = KVStoreModel()
        for h in sorted(self.blocks):
            for tx in self.blocks[h]:
                model.deliver(tx)
        acked = [r["tx"] for r in self.sent
                 if r.get("code") == 0 and r["tx"] in self.where]
        step = max(1, len(acked) // self.p["readback_sample"])
        sample = acked[::step]
        conns = [Conn(self.port) for _ in range(32)]

        async def read(conn, txs):
            wrong = 0
            for tx in txs:
                key = KVStoreModel.key_of(tx)
                r = await self.ask(conn, "abci_query", path="",
                                   data=key.hex())
                got = base64.b64decode(r["response"]["value"] or "")
                wrong += got != model.values.get(key)
            return wrong

        lost = None
        if control == "acked_write_lost":
            # a node that acknowledged a write and then lost it: the
            # model of that node answers in the app's place
            lost = sample[len(sample) // 2]
        wrong = sum(await asyncio.gather(
            *(read(c, sample[i::len(conns)]) for i, c in enumerate(conns))))
        if lost is not None:
            wrong += 1
        info = await self.ask(conns[0], "abci_info")
        for c in conns:
            c.close()
        resp = info["response"]
        app_size = json.loads(resp["data"])["size"]
        app_hash = base64.b64decode(resp["last_block_app_hash"] or "")
        return {
            "acked_committed_txs_not_read_back": (wrong, 0),
            "bad_signature_txs_not_refused_429": (sum(
                1 for c in codes.values()
                if c != CODE_ADMISSION_REJECT), 0),
            "bad_signature_txs_in_a_block": (sum(
                1 for tx in codes if tx in self.where), 0),
            "app_tx_count_minus_model": (abs(app_size - model.size), 0),
            "app_hash_differs_from_model": (
                int(app_hash != model.app_hash()), 0),
            "_facts": {"read_back": len(sample), "acked_committed":
                       len(acked), "bad_signature_txs": len(bad),
                       "blocks": len(self.blocks), "model_size": model.size},
        }


async def serve() -> None:
    loop = asyncio.get_running_loop()
    hello = json.loads(await loop.run_in_executor(None, sys.stdin.readline))
    lg = LoadGen(hello["port"], hello["seed"], hello["params"])
    print(json.dumps({"event": "ready"}), flush=True)
    while True:
        line = await loop.run_in_executor(None, sys.stdin.readline)
        if not line:
            return
        cmd = json.loads(line)
        if cmd["cmd"] == "quit":
            return
        if cmd["cmd"] == "burst":
            out = await lg.burst(cmd["n"])
        elif cmd["cmd"] == "run":
            out = await lg.run(cmd["seconds"])
        elif cmd["cmd"] == "check":
            out = await lg.check(cmd.get("control"))
        else:
            out = {"error": f"unknown command {cmd['cmd']!r}"}
        print(json.dumps({"event": "done", "cmd": cmd["cmd"], "out": out}),
              flush=True)


if __name__ == "__main__":
    assert "jax" not in sys.modules
    asyncio.run(serve())
    assert "jax" not in sys.modules, "the load generator imported jax"
