"""Traffic: the light proxy as `cmd light` serves it (a ServingPool over
ONE serving plane sized by `[light]`, its launch shapes loaded before
the ports open: `cmd.start_light_pool`), in front of a scripted primary
and two scripted witnesses, with a closed loop of callers over real TCP
JSON-RPC from a child process that never imports JAX
(`light_loadgen.py`). This process owns the chip: only it can take the
profiler trace.

The providers are the program's `RPCProvider`s with a scripted node
where their HTTP client stands: it hands over the encoded `/commit` and
`/validators` bodies of a seeded chain (`light_chain.py`) and the
provider decodes them as it does a node's (TCP to the providers is
bypassed, the decode is not). For one height in `planted_every` the
primary's FIRST answer of a replay is forged.

A replay: the callers scan heights 2 -> top through one shared cursor
and ask for the newest header one time in `latest_one_in`; when the
cursor has passed the top and every reply is in, the proxy is closed
and a fresh one (fresh store, same trust root, programs already
loaded) takes the next.

Parameters (the cell's file): the chain's (`light_chain.py`), `clients`,
`connections`, `latest_one_in`, `warm_requests`, `reference_sample`,
`trust_period_h`.
"""

from __future__ import annotations

import asyncio
import json
import os
import queue
import shutil
import subprocess
import sys
import threading
import time

from benchmark import gen
from benchmark.harness import OUT, REPO, BenchFailure, say
from benchmark.reference import ed25519_zip215 as ref
from benchmark.reference import light_model as model
from benchmark.traffic import light_chain

# the program's error classes, as the model names a refusal
KIND_OF = {"VerificationFailedError": model.INVALID,
           "ValueError": model.INVALID,
           "NewValSetCantBeTrustedError": model.CANT_BE_TRUSTED,
           "OutsideTrustingPeriodError": model.EXPIRED,
           "LightClientError": model.BACKWARDS}


class Journal(list):
    """The plane's journal (ServingPlane.journal) and the scripted
    primary's fetches in ONE order, each stamped with the clock the
    load generator reads too (CLOCK_MONOTONIC is the machine's)."""

    def append(self, event: tuple) -> None:
        super().append(event + (time.monotonic(),))


class ScriptedNode:
    """Stands where an RPCProvider's HTTP client stands: `call` hands
    over a node's answer, decoded from its JSON text as the client
    decodes a response body. `forged` (the primary's): height -> the
    bodies of a forged block, handed over ONCE, as the first answer."""

    def __init__(self, bodies: dict, top: int, forged: dict | None = None,
                 kinds: dict | None = None, journal: list | None = None):
        self.bodies, self.top = bodies, top
        self.forged = dict(forged or {})
        self.kinds = kinds or {}
        self.journal = journal
        self._serving: dict[int, tuple] = {}

    async def call(self, method: str, **params):
        from tendermint_tpu.rpc.jsonrpc import RPCError

        height = int(params.get("height") or self.top)
        if height not in self.bodies:
            raise RPCError(-32603, f"height {height} is not available")
        if method == "commit":
            answer = self.forged.pop(height, None)
            if self.journal is not None:
                self.journal.append(
                    ("fetch", height,
                     self.kinds.get(height) if answer else None))
            self._serving[height] = answer or self.bodies[height]
            return json.loads(self._serving[height][0])
        if method == "validators":
            pages = self._serving.get(height, self.bodies[height])[1]
            return json.loads(pages[int(params.get("page", 1)) - 1])
        raise RPCError(-32601, f"method {method!r} not found")


def provider(node: ScriptedNode, name: str):
    from tendermint_tpu.light.provider import RPCProvider

    p = RPCProvider("127.0.0.1", 0, name)
    p.client = node
    return p


class Replayer:
    """`reference/light_model.py` replayed beside one proxy's journal:
    at every event the model decides for itself (the case, the trusted
    block, the lanes of each check, the verdict, the pivot, what is
    stored) and every difference is counted."""

    def __init__(self, chain, verify_sig, now_ns: int, period_ns: int):
        self.chain = chain
        self.model = model.LightModel(chain.chain_id, period_ns,
                                      verify_sig=verify_sig)
        root = chain.blocks[1]
        self.model.initialize(root, model.block_hash(root))
        # lanes a proxy's launches hold: the root's own check at its
        # start, then both plans of every step once both are built
        self.root_lanes = len(self.model.light_lanes(root))
        self.launched = 0
        self.now_ns = now_ns
        self.reqs: dict[int, dict] = {}
        self.cur = None
        self.wrong: list[str] = []
        self.lanes_ok: dict[int, int] = {}   # rid -> lanes verified ok
        self.walks_after_latest = 0
        self.latest_done = False
        self.steps = 0

    def _bad(self, what: str, event) -> None:
        if len(self.wrong) < 20:
            self.wrong.append(f"{what}: {event[:-1]}")
        else:
            self.wrong.append("")

    def feed(self, event: tuple) -> None:
        kind = event[0]
        if kind == "verify":
            _, rid, height, _ = event
            r = self.reqs[rid] = {"height": height, "pending": [],
                                  "seen": set(), "expect": None,
                                  "trusted": None, "walk": False}
            self.cur = r
            m = self.model
            if height and height in m.store:
                r["expect"] = ("done", height, None)
            elif height == 0:
                r["expect"] = ("fetch", self.chain.top)
            else:
                case, base = m.begin(height)
                if case == model.BACKWARD:
                    r["walk"] = True
                    r["expect"] = ("walk",)
                else:
                    r["trusted"] = m.store[base]
                    r["expect"] = ("fetch", height)
        elif kind == "fetch":
            _, height, forged, _ = event
            r = self.cur
            if r is None or r["walk"]:
                return
            if r["expect"] != ("fetch", height):
                return self._bad(f"fetch, expected {r and r['expect']}",
                                 event)
            block = self.chain.forged[height] if forged \
                else self.chain.blocks[height]
            if r["height"] == 0 and not r["pending"] \
                    and r["trusted"] is None:
                top = self.model.heights()[-1]
                if height <= top:
                    r["expect"] = ("done", top, None)
                    return
                r["trusted"] = self.model.store[top]
            r["pending"].append(block)
            r["seen"].add(height)
            r["expect"] = ("step",)
        elif kind == "step":
            _, rid, t_h, b_h, lanes_t, lanes_o, outcome, _ = event
            r = self.cur = self.reqs.get(rid)
            self.steps += 1
            if r is None or r["expect"] != ("step",):
                return self._bad(f"step, expected {r and r['expect']}",
                                 event)
            trusted, block = r["trusted"], r["pending"][-1]
            if (t_h, b_h) != (trusted["header"]["height"],
                              block["header"]["height"]):
                return self._bad("step between other heights", event)
            try:
                want = (*self.model.step(trusted, block, self.now_ns), "ok")
            except model.Refused as e:
                want = (*self.model.refused_lanes, e.kind)
            got = (lanes_t, lanes_o,
                   "ok" if outcome == "ok" else KIND_OF.get(outcome, outcome))
            if got != want:
                self._bad(f"step, the model says {want}", event)
            # a check whose tally fails builds no plan, and then
            # neither plan of the step is launched
            self.launched += want[0] + want[1] if want[1] else 0
            if want[2] == "ok":
                self.model.trust(block)
                self.lanes_ok[rid] = self.lanes_ok.get(rid, 0) \
                    + want[0] + want[1]
                r["trusted"] = block
                r["pending"].pop()
                r["expect"] = ("step",) if r["pending"] else (
                    "done", b_h, None)
            elif want[2] == model.CANT_BE_TRUSTED and (
                    (t_h + b_h) // 2 not in (t_h, b_h)
                    and (t_h + b_h) // 2 not in r["seen"]):
                r["expect"] = ("fetch", (t_h + b_h) // 2)
            else:
                r["expect"] = ("done", None, want[2])
        elif kind == "walk":
            _, rid, height, _ = event
            r = self.reqs.get(rid)
            if r is None or not r["walk"]:
                self._bad("a hash walk where the model makes none", event)
            else:
                self.model.trust(self.chain.blocks[height])
                r["expect"] = ("done", height, None)
            self.walks_after_latest += self.latest_done
        elif kind == "done":
            _, rid, served, error, _ = event
            r = self.reqs.get(rid)
            got = ("done", served, KIND_OF.get(error, error))
            if r is None or r["expect"] != got:
                self._bad(f"done, expected {r and r['expect']}", event)
            if r is not None and r["height"] == 0 and error is None:
                self.latest_done = True


class Driver:
    CONTROLS = ("no_signature_check", "own_check_only",
                "serves_unverified")

    def __init__(self, run):
        self.run = run
        self.loop = None
        self.child = None
        self.pool = None
        self.home = os.path.join(OUT, "light-" + run.cell.name)
        self.replays: list[dict] = []   # one a proxy: journal, store
        self.opened = 0                 # proxies so far: a store each
        self.window = None

    # ------------------------------------------------------ the proxy

    def _on_loop(self, coro, timeout=None):
        return asyncio.run_coroutine_threadsafe(coro, self.loop).result(
            timeout)

    def _config(self):
        from tendermint_tpu.config import Config, LightConfig

        cfg = Config()
        cfg.base.home = self.home
        c, shipped = self.run.config, LightConfig()
        for key, value in c["config_toml_overrides"].items():
            section, field = key.split(".")
            setattr(getattr(cfg, section), field, value)
        got = {k: getattr(shipped, k) for k in c["light"]}
        if got != c["light"]:
            raise BenchFailure(f"[light] defaults moved: {got}")
        if self.run.rehearse:
            cfg.crypto.backend = "auto"  # no chip to promise
            cfg.light.batch_max = self.run.params["batch_max"]
        cfg.validate_basic()
        return cfg

    async def _open(self) -> list[int]:
        """A fresh proxy, as `cmd light` builds it: fresh store on the
        default durable db, the trust root, the plane from [light]."""
        from tendermint_tpu import cmd
        from tendermint_tpu.light import Client, LightStore, TrustOptions

        chain = self.chain
        self.opened += 1
        journal = Journal()
        primary = ScriptedNode(self.bodies, chain.top, self.forged_bodies,
                               chain.planted, journal)
        path = os.path.join(self.home, f"light-{self.opened}.sqlite")
        client = Client(
            chain.chain_id,
            TrustOptions(period_ns=self.period_ns, height=1,
                         hash=model.block_hash(chain.blocks[1])),
            provider(primary, "primary"),
            [provider(ScriptedNode(self.bodies, chain.top), f"witness{i}")
             for i in range(self.run.config["witnesses"])],
            LightStore(cmd.light_store_db(self.cfg, path)))
        self.pool = await cmd.start_light_pool(
            self.cfg, client, "127.0.0.1", 0)
        self.pool.plane.journal = journal
        self.replays.append({"journal": journal, "client": client})
        return self.pool.ports

    def _snapshot(self) -> None:
        """What the newest proxy's store and plane hold, for check()."""
        rep, plane = self.replays[-1], self.pool.plane
        store = rep["client"].store
        rep["stored"] = store.heights()
        rep["planted_hashes"] = {
            h: store.get(h).hash() for h in self.chain.planted
            if h in set(rep["stored"])}
        rep["tallies"] = {"requests": plane.requests,
                          "hash_walks": plane.hash_walks,
                          "steps": plane.steps, "sheds": dict(plane.sheds)}

    async def _close(self) -> None:
        """End a replay: its snapshot, then the proxy goes."""
        self._snapshot()
        store = self.replays[-1].pop("client").store
        self.pool.close()
        store.db.close()
        self.pool = None

    def setup(self) -> None:
        from tendermint_tpu import cmd
        from tendermint_tpu.light.store import LightStore

        if not hasattr(cmd, "start_light_pool") or \
                not hasattr(LightStore, "light_block_before"):
            raise BenchFailure(
                "this program cannot run the cell: `cmd light` has no "
                "serving pool (cmd.start_light_pool) or the light store "
                "no light_block_before")
        run, p = self.run, self.run.params
        shutil.rmtree(self.home, ignore_errors=True)
        os.makedirs(self.home, exist_ok=True)
        self.cfg = self._config()
        self.period_ns = p["trust_period_h"] * 3600 * 10**9
        t0 = time.perf_counter()
        pool = gen.make_pool()
        try:
            self.chain = light_chain.Chain(
                run.seed, p, light_chain.pool_signer(pool, run.seed),
                time.time_ns())
        finally:
            pool.shutdown(wait=True)
        chain = self.chain

        def bodies(block):
            return (light_chain.commit_body(block),
                    light_chain.validators_bodies(block))

        self.bodies = {h: bodies(b) for h, b in chain.blocks.items()}
        self.forged_bodies = {h: bodies(b) for h, b in chain.forged.items()}
        say("chain made", heights=chain.top, signatures=chain.signatures,
            keys=len(chain.pubs), planted=len(chain.planted),
            body_bytes=sum(len(c) + sum(map(len, v))
                           for c, v in self.bodies.values()),
            seconds=round(time.perf_counter() - t0, 3))

        self.loop = asyncio.new_event_loop()
        self._thread = threading.Thread(
            target=self.loop.run_forever, name="bench-light", daemon=True)
        self._thread.start()
        t0 = time.perf_counter()
        ports = self._on_loop(self._open(), timeout=1500)
        from tendermint_tpu.libs.tracing import LIGHT_LOAD_PROGRAMS, TRACER

        say("proxy serving", ports=ports,
            shapes=[self.pool.plane.collector.shapes.lanes],
            load_programs=[r[6] for r in TRACER.snapshot()
                           if r[0] == LIGHT_LOAD_PROGRAMS],
            seconds=round(time.perf_counter() - t0, 3))
        self.child = subprocess.Popen(
            [sys.executable, os.path.join(REPO, "benchmark", "traffic",
                                          "light_loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        self._lines: queue.Queue = queue.Queue()
        threading.Thread(target=self._read_child, daemon=True).start()
        self._tell({"ports": ports, "seed": run.seed, "params": p,
                    "hashes": {str(h): model.block_hash(b).hex()
                               for h, b in chain.blocks.items()}})
        self._hear("ready")

    def _read_child(self) -> None:
        for line in self.child.stdout:
            self._lines.put(line)
        self._lines.put(None)

    def _tell(self, obj: dict) -> None:
        self.child.stdin.write(json.dumps(obj) + "\n")
        self.child.stdin.flush()

    def _next(self, timeout: float = 600.0) -> dict:
        """The child's next line; the launch ledger drained meanwhile
        (its ring holds 512 launches)."""
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            try:
                line = self._lines.get(timeout=0.2)
            except queue.Empty:
                self.run.ledger.drain()
                continue
            if line is None:
                raise BenchFailure(
                    f"the load generator died (rc={self.child.poll()})")
            return json.loads(line)
        raise BenchFailure("the load generator went silent")

    def _hear(self, event: str) -> dict:
        msg = self._next()
        if msg.get("event") != event:
            raise BenchFailure(f"load generator said {msg}, not {event}")
        return msg

    def _drive(self, **cmd) -> dict:
        """A `run` of the child: a fresh proxy for every replay it
        ends, then its report."""
        self._tell(dict(cmd, cmd="run"))
        while True:
            msg = self._next()
            if msg.get("event") == "replay_done":
                self._on_loop(self._close(), timeout=120)
                ports = self._on_loop(self._open(), timeout=600)
                self._tell({"cmd": "ports", "ports": ports})
            elif msg.get("event") == "window_closed":
                self.run.ledger.drain()
            elif msg.get("event") == "done":
                return msg["out"]
            else:
                raise BenchFailure(f"load generator said {msg}")

    # ------------------------------------------------------------ run

    def warm(self) -> None:
        """The launch shapes are the plane's own and were loaded when
        it started; a short run through a first proxy warms the rest
        (the routes, the decoders, sqlite), and the window begins on a
        fresh one."""
        t0 = time.perf_counter()
        before = self.run.compiles.count
        out = self._drive(requests=self.run.params["warm_requests"])
        self._on_loop(self._close(), timeout=120)
        self.replays.clear()
        ports = self._on_loop(self._open(), timeout=600)
        self._tell({"cmd": "ports", "ports": ports, "replay": 0})
        self._hear("done")
        self.run.ledger.drain()
        say("warm run", replies=len(out["replies"]),
            right=sum(1 for r in out["replies"] if r.get("right")),
            compiles=self.run.compiles.count - before,
            shapes=self._shapes(self.run.ledger.records),
            seconds=round(time.perf_counter() - t0, 3))

    @staticmethod
    def _shapes(records) -> dict:
        out: dict = {}
        for r in records:
            g = out.setdefault(f"{r['workload']}/{r['kernel']}", {})
            g[r["capacity"]] = g.get(r["capacity"], 0) + 1
        return out

    def measure(self, seconds: float) -> dict:
        run = self.run
        first_record = self.first_record = len(run.ledger.records)
        compiles_before = run.compiles.count
        out = self._drive(seconds=seconds)
        run.ledger.drain()

        async def snapshot():
            self._snapshot()

        self._on_loop(snapshot(), timeout=60)
        t0, t1 = out["t0"], out["t0"] + seconds
        replies = out["replies"]
        inside = [r for r in replies if t0 <= r["t1"] <= t1]
        self.window = {"replies": replies, "inside": inside}
        # lanes verified for requests that were then answered rightly
        self.replayers = self._replay_all(None)
        right = {(r["r"], r.get("served")) for r in replies
                 if r.get("right")}
        sigs = None
        if len(inside) >= 2:
            first = min(r["t1"] for r in inside)
            last = max(r["t1"] for r in inside)
            lanes = 0
            for n, (rep, rp) in enumerate(zip(self.replays, self.replayers)):
                for ev in rep["journal"]:
                    if ev[0] == "done" and ev[3] is None \
                            and first < ev[-1] <= last \
                            and (n, ev[2]) in right:
                        lanes += rp.lanes_ok.get(ev[1], 0)
            sigs = lanes / (last - first)
            self.window.update(lanes=lanes, between_s=last - first)
        refused_first = sum(1 for r in inside
                            if "error" in r and not r["again"])
        failed = sum(1 for r in inside if ("error" in r and r["again"])
                     or ("served" in r and not r["right"]))
        run.samples["request_ms"] = [
            (r["t1"] - r["t0"]) * 1e3 for r in inside if r.get("right")]
        records = run.ledger.records[first_record:]
        say("window", replies=len(inside), right=sum(
            1 for r in inside if r.get("right")),
            latest=sum(1 for r in inside if r["h"] == 0),
            refused_forgeries=refused_first, failed=failed,
            replays=len(self.replays), sigs_per_s=sigs,
            lanes=self.window.get("lanes"),
            between_s=self.window.get("between_s"),
            launches=len(records), shapes=self._shapes(records),
            compiled_in_window=run.compiles.count - compiles_before,
            steps=sum(rp.steps for rp in self.replayers),
            tallies=[r["tallies"] for r in self.replays])
        if sigs is None:
            raise BenchFailure("the window held fewer than two replies")
        return {"attempted": len(inside), "failed": failed,
                "metrics": {"sigs_per_s": sigs}}

    # ---------------------------------------------------------- check

    def _verifier(self, keep: bool):
        """The model's signature check: the generator's own record (an
        OpenSSL signature over exactly these bytes, or a spoiled one);
        `keep` the lanes it is asked about, for _reference_lanes."""
        signed, lanes = self.chain.signed, []

        def verify(pub: bytes, msg: bytes, sig: bytes) -> bool:
            if keep:
                lanes.append((pub, msg, sig))
            return signed.get((pub, msg)) == sig

        return verify, lanes

    def _reference_lanes(self, lanes: list) -> dict:
        """The copied ZIP-215 verifier on every spoiled lane and on a
        seeded sample of the others, against the record the model
        took: a lane where they disagree is counted."""
        spoiled = set(self.chain.spoiled.values())
        picks = {i for i, lane in enumerate(lanes) if lane[2] in spoiled}
        rest = self.run.params["reference_sample"] - len(picks)
        if rest > 0 and lanes:
            picks.update(self.run.rng("ref-lanes").choice(
                len(lanes), size=min(rest, len(lanes)),
                replace=False).tolist())
        signed = self.chain.signed
        disagree = sum(
            ref.verify(*lanes[i]) != (signed.get(lanes[i][:2])
                                      == lanes[i][2]) for i in picks)
        return {"seen": len(lanes), "checked": len(picks),
                "spoiled": sum(1 for i in picks if lanes[i][2] in spoiled),
                "disagree": disagree}

    def _launched_lanes(self) -> dict:
        """Signature lanes the plane's launches held since the window's
        proxy began to serve: `device`, the launch ledger's records
        tagged `light` (a record's lanes less the sentinel's), and
        `host`, the lanes of the flushes the host answered (a cut under
        the device threshold; `light.flush` {lanes, backend}). On the
        chip every launch is the ledger's: the gate fails a run on a
        host fallback."""
        from tendermint_tpu.libs.tracing import LIGHT_FLUSH, TRACER

        records = [r for r in self.run.ledger.records[self.first_record:]
                   if r["workload"] == "light"]
        return {"device": sum(r["lanes"] - 1 for r in records),
                "host": sum(r[6].get("lanes", 0) for r in TRACER.snapshot()
                            if r[0] == LIGHT_FLUSH
                            and r[6].get("backend") == "host")}

    def _replay_all(self, control: str | None) -> list:
        verify, lanes = self._verifier(keep=control is None)
        out = []
        for rep in self.replays:
            rp = Replayer(self.chain, verify, time.time_ns(),
                          self.period_ns)
            for event in self._weakened(rep["journal"], control):
                rp.feed(event)
            out.append(rp)
        if control is None:
            self._ref = self._reference_lanes(lanes)
        return out

    def _weakened(self, journal: list, control: str | None) -> list:
        """What a weakened plane's journal would hold in this one's
        place: the forged first answers its weakness lets through are
        verified in ONE step that ends well, and served."""
        let_through = {
            None: (),
            "no_signature_check": ("sig_bit", "s_plus_l"),
            "own_check_only": ("foreign",),
            "serves_unverified": light_chain.FORGED}[control]
        if not let_through:
            return journal
        out, skip = [], {}
        for i, ev in enumerate(journal):
            if ev[0] == "fetch" and ev[2] in let_through:
                rid = next((e[1] for e in reversed(journal[:i])
                            if e[0] in ("verify", "step")), None)
                skip[rid] = ev[1]
                out.append(ev)
                continue
            if len(ev) > 2 and ev[1] in skip and ev[0] in ("step", "done"):
                if ev[0] == "step" and ev[3] == skip[ev[1]] \
                        and control != "serves_unverified":
                    out.append(ev[:6] + ("ok",) + ev[7:])
                elif ev[0] == "done":
                    out.append(("done", ev[1], skip.pop(ev[1]), None, ev[-1]))
                continue
            out.append(ev)
        if not skip and not any(ev[0] == "fetch" and ev[2] in let_through
                                for ev in journal):
            # the proxy met no such forgery: ask the weakened plane for
            # one, and it hands the forged header through
            height = next(h for h, k in self.chain.planted.items()
                          if k in let_through)
            now = time.monotonic()
            out += [("verify", -1, height, now),
                    ("fetch", height, self.chain.planted[height], now),
                    ("done", -1, height, None, now)]
        return out

    def check(self, control: str | None = None) -> dict:
        chain, w = self.chain, self.window
        replayers = self.replayers if control is None \
            else self._replay_all(control)
        refused = [r for r in w["replies"]
                   if "error" in r and not r["again"]]
        wrong_replies = sum(1 for r in w["replies"]
                            if "served" in r and not r["right"])
        if control == "serves_unverified":
            # the forged headers go out in place of the refusals
            wrong_replies += len(refused)
        forged_stored = walks = unlike = 0
        for rep, rp in zip(self.replays, replayers):
            forged_stored += sum(
                1 for h, got in rep["planted_hashes"].items()
                if got != model.block_hash(chain.blocks[h]))
            unlike += rep["stored"] != rp.model.heights()
            walks += rp.walks_after_latest
        launched = self._launched_lanes()
        # the window's first proxy pinned its root before the window
        modelled = sum(rp.launched for rp in replayers) \
            + sum(rp.root_lanes for rp in replayers[1:])
        fetched_forged = sum(1 for rep in self.replays
                             for ev in rep["journal"]
                             if ev[0] == "fetch" and ev[2])
        return {
            "replies_whose_header_is_not_the_chains": (wrong_replies, 0),
            "journal_events_the_model_decides_otherwise": (
                sum(len(rp.wrong) for rp in replayers), 0),
            "stores_that_differ_from_the_models": (unlike, 0),
            "forged_blocks_in_a_store": (forged_stored, 0),
            "scan_requests_served_by_a_hash_walk_after_the_first_latest":
                (walks, 0),
            "forged_first_answers_not_refused": (
                abs(fetched_forged - sum(
                    1 for rep in self.replays for ev in rep["journal"]
                    if ev[0] == "done" and ev[3] is not None)), 0),
            "signature_lanes_launched_beside_the_models": (
                abs(sum(launched.values()) - modelled), 0),
            "lanes_where_the_reference_verifier_disagrees": (
                self._ref["disagree"], 0),
            "reference_lanes_short_of_the_sample": (
                max(0, self.run.params["reference_sample"]
                    - self._ref["checked"]), 0),
            "_facts": {
                "replies": len(w["replies"]), "refused_once": len(refused),
                "forged_first_answers": fetched_forged,
                "steps_replayed": sum(rp.steps for rp in replayers),
                "reference_lanes": self._ref["checked"],
                "spoiled_lanes_among_them": self._ref["spoiled"],
                "lanes_seen": self._ref["seen"],
                "lanes_launched": launched, "lanes_modelled": modelled,
                "first_differences": [x for rp in replayers
                                      for x in rp.wrong if x][:6]},
        }

    def close(self) -> None:
        if self.child is not None and self.child.poll() is None:
            try:
                self._tell({"cmd": "quit"})
                self.child.wait(timeout=20)
            except (OSError, subprocess.TimeoutExpired):
                self.child.kill()
                self.child.wait()
        if self.loop is not None:
            async def stop():
                if self.pool is not None:
                    self.pool.close()
                rest = [t for t in asyncio.all_tasks()
                        if t is not asyncio.current_task()]
                for t in rest:
                    t.cancel()
                await asyncio.gather(*rest, return_exceptions=True)

            try:
                self._on_loop(stop(), timeout=30)
            except Exception as e:  # the result is already decided
                say("proxy stop failed", error=repr(e))
            self.loop.call_soon_threadsafe(self.loop.stop)
            self._thread.join(timeout=10)
            self.loop.close()
