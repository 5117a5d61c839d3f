"""What every cell shares: the manifest, the gate that keeps a number
that did not come from the chip out of the result, the launch-ledger
drain, the harness's own spans, the profiler slice and the result line.

Nothing here knows a configuration, a traffic mix or a metric by name:
those are files found by the names in BENCHMARK.json (README.md).
"""

from __future__ import annotations

import contextlib
import glob
import importlib
import json
import os
import shutil
import statistics
import threading
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)
OUT = os.path.join(BENCH, "out")


class BenchFailure(Exception):
    """The run may not print a result line."""


_WHERE = "?"   # platform/device_kind/count, once JAX has been asked


def say(msg: str, **facts) -> None:
    """An earlier line: facts of this run, never the result. Every
    line names where it ran."""
    print(f"bench[{_WHERE}]: {msg}" + (" " + json.dumps(facts, default=str)
                                      if facts else ""), flush=True)


# ------------------------------------------------------------- manifest


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of BENCHMARK.json `workloads` with its files: the
    deployment (`configs/<config>.json`), the traffic mix
    (`workloads/<cell>.json`, whose `driver` names the generator in
    `traffic/`) and the metrics that list it."""

    def __init__(self, name: str):
        manifest = _load(os.path.join(REPO, "BENCHMARK.json"))
        entry = next((w for w in manifest["workloads"]
                      if w["name"] == name), None)
        if entry is None:
            raise BenchFailure(f"no cell {name!r} in BENCHMARK.json")
        self.name = name
        self.chips = entry["chips"]
        cfg = next(c for c in manifest["configs"]
                   if c["name"] == entry["config"])
        self.config = _load(os.path.join(REPO, cfg["file"]))
        self.mix = _load(os.path.join(BENCH, "workloads", name + ".json"))
        self.params = self.mix["params"]

        def mine(metrics):
            return [m for m in metrics
                    if name in m.get("workloads", [name])]

        self.end_to_end = mine(manifest["end_to_end"])
        self.per_layer = mine(manifest["per_layer"])

    def driver(self):
        return importlib.import_module(
            f"benchmark.traffic.{self.mix['driver']}")


# ----------------------------------------------------------------- gate


def chip_faults(records, platform: str) -> list[str]:
    """Why this run may NOT be read as a run on `platform` (copied from
    tools/bench_util.chip_faults): ledger records that landed on
    another device, raised or failed their sentinel; a non-zero
    tpu_host_fallbacks_total; a breaker that is not closed."""
    from tendermint_tpu.crypto import batch
    from tendermint_tpu.libs.metrics import tpu_metrics

    faults = []
    for r in records:
        where = f"{r['workload']}/{r['kernel']} launch"
        if platform not in str(r["device"]).lower():
            faults.append(f"{where} landed on {r['device']!r}")
        if r["verdict"] in ("raised", "sentinel_failed"):
            faults.append(f"{where} {r['verdict']}: {r.get('error')}")
    fallbacks = tpu_metrics().host_fallbacks.value()
    if fallbacks:
        faults.append(f"tpu_host_fallbacks_total = {fallbacks:g}")
    faults += [f"{name} breaker is {state}"
               for name, state in batch.breaker_states().items()
               if state != "closed"]
    return sorted(set(faults))


class CompileWatch:
    """Counts XLA backend compiles (cache loads included: JAX times
    both under the same event) so that one inside the window fails the
    run instead of passing as a slow operation."""

    def __init__(self):
        from jax import monitoring

        self.count = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.count += 1
            self.seconds += secs


class LedgerDrain:
    """Copies launch records out of the program's 512-record ring
    before a busy window evicts them; `lost` counts what it missed."""

    def __init__(self):
        from tendermint_tpu.crypto.tpu import ledger

        self._ledger = ledger
        self._seen = ledger.evicted() + len(ledger.snapshot())
        self.records: list[dict] = []
        self.lost = 0

    def drain(self) -> None:
        snap = self._ledger.snapshot()
        total = self._ledger.evicted() + len(snap)
        new = total - self._seen
        self._seen = total
        if new > len(snap):
            self.lost += new - len(snap)
            new = len(snap)
        if new > 0:
            self.records.extend(snap[-new:])


# ------------------------------------------------------ profiler slice


class TraceSlice:
    """The profiler, on for `length_s` in the middle of the window (a
    whole window of a 10,240-lane program is too many device events).
    A `bench:clock_sync` annotation stamped with perf_counter_ns lets
    the reduction lay the program's own spans on the trace's clock.

    Stopping the profiler is the dear part: 50 s after 0.8 s, 108 s
    after 1.5 s, 145 s after 2.0 s and ~195 s after 3.0 s of these
    cells (one launch of a verify program is 54,000-134,000 device
    events whatever its lanes), in the window or after it, and a run
    has 360 s in all. A cell whose launches come
    in bursts gives `launches` (its `trace_slice_launches`): once the
    profiler has been on for `ARM_S` (a process's first session loses
    the module events of launches that begin before that), the slice
    ends as soon as the launch ledger has grown by that many records,
    and at `length_s` at the latest."""

    ARM_S = 0.3

    def __init__(self, name: str, window_s: float, length_s: float,
                 launches: int | None = None):
        self.dir = os.path.join(OUT, "trace", name)
        shutil.rmtree(self.dir, ignore_errors=True)
        self.length_s = min(length_s, window_s)
        self.delay_s = (window_s - self.length_s) / 2
        self.launches = launches
        self.sync_ns = None
        self.window_s = None
        self.stop_s = None
        self.error = None
        self._thread = threading.Thread(target=self._run,
                                        name="bench-trace", daemon=True)

    def start(self) -> None:
        self._thread.start()

    def _run(self) -> None:
        import jax

        try:
            time.sleep(self.delay_s)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            t0 = time.perf_counter()
            self.sync_ns = time.perf_counter_ns()
            with jax.profiler.TraceAnnotation("bench:clock_sync"):
                pass
            self._hold(t0)
            self.window_s = time.perf_counter() - t0
            jax.profiler.stop_trace()
            self.stop_s = time.perf_counter() - t0 - self.window_s
        except Exception as e:  # reported by finish(); fails the run
            self.error = e

    def _hold(self, t0: float) -> None:
        if self.launches is None:
            time.sleep(self.length_s)
            return
        from tendermint_tpu.crypto.tpu import ledger

        def count():
            return ledger.evicted() + len(ledger.snapshot())

        time.sleep(min(self.ARM_S, self.length_s))
        until = count() + self.launches
        while (time.perf_counter() - t0 < self.length_s
               and count() < until):
            time.sleep(0.005)

    def finish(self) -> str:
        self._thread.join(timeout=300)
        if self._thread.is_alive() or self.error is not None:
            raise BenchFailure(f"profiler slice failed: {self.error!r}")
        found = glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        if not found:
            raise BenchFailure("the profiler wrote no trace")
        return found[0]


# ------------------------------------------------------------------ run


class Run:
    """What a traffic driver is handed: the cell, the seed, whether the
    run is traced, the harness's span recorder and the ledger drain."""

    def __init__(self, cell: Cell, seed: int, seconds: float,
                 trace: bool, rehearse: bool, t_start: float):
        self.cell = cell
        self.config = cell.config
        self.params = dict(cell.params)
        if rehearse:
            self.params.update(cell.mix.get("rehearse", {}))
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.rehearse = rehearse
        self.t_start = t_start
        self.spans: dict[str, list[tuple[int, int]]] = {}
        self.counters: dict[str, float] = {}
        self.samples: dict[str, list[float]] = {}
        self.ledger: LedgerDrain | None = None
        self.compiles: CompileWatch | None = None
        self.window_ns: tuple[int, int] | None = None

    @contextlib.contextmanager
    def span(self, name: str):
        """A harness span around a call into a layer: recorded in the
        traced run only, and written into the profiler's trace too."""
        if not self.trace:
            yield
            return
        import jax

        t0 = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation("bench:" + name):
            try:
                yield
            finally:
                self.spans.setdefault(name, []).append(
                    (t0, time.perf_counter_ns() - t0))

    def rng(self, purpose: str):
        """A generator that depends on the seed and on nothing else."""
        import numpy as np

        tag = int.from_bytes(purpose.encode()[:8].ljust(8, b"\0"), "little")
        return np.random.default_rng([self.seed, tag])


def pctl(values, p: float) -> float:
    """The p-th percentile (nearest rank) of all the values."""
    vals = sorted(values)
    if not vals:
        raise BenchFailure("no samples for a percentile")
    return vals[min(len(vals) - 1, int(p / 100.0 * len(vals)))]


def median(values) -> float:
    return statistics.median(values)


def stage_medians(records) -> dict:
    """{workload/kernel: {stage: median ms}} of launch-ledger records:
    printed with every run so that a slow run says where."""
    groups: dict[str, dict[str, list]] = {}
    for r in records:
        g = groups.setdefault(f"{r['workload']}/{r['kernel']}", {})
        for stage, ms in r["stages_ms"].items():
            g.setdefault(stage, []).append(ms)
    return {k: {st: round(median(v), 3) for st, v in g.items()}
            for k, g in groups.items()}


def device_facts(chips: int, rehearse: bool) -> dict:
    """Fail at once without the chips the cell asks for."""
    import jax

    global _WHERE
    devs = jax.devices()
    d0 = devs[0]
    _WHERE = f"{d0.platform}/{d0.device_kind}/{len(devs)}"
    if not rehearse:
        if d0.platform != "tpu":
            raise BenchFailure(
                f"the benchmark measures the chip: the default JAX "
                f"backend is {d0.platform!r} ({d0.device_kind})")
        if len(devs) < chips:
            raise BenchFailure(
                f"the cell asks for {chips} chips, JAX has {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))
