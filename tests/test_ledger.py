"""Device launch ledger + silicon watchdog (crypto/tpu/{ledger,
watchdog}.py; docs/OBSERVABILITY.md "Launch ledger & silicon
watchdog").

Pins the observability contract end to end:

  * the ring is bounded and counts evictions;
  * EVERY dispatch site — verify.verify_batch chunks,
    ExpandedKeys._traced_verify, ResidentArena.launch /
    MeshResidentArena.launch, verify_batch_sr — emits exactly one
    record per launch (fake kernels: the contract is the record, not
    the crypto);
  * arena records carry DELTA H2D bytes (splices + templates since the
    last launch), byte-exact;
  * with crypto.backend=tpu configured and launches landing on CPU or
    raising, the /status device check degrades WITHIN ONE LAUNCH with
    effective_backend=cpu_fallback (and the one-hot gauge flips), then
    recovers after one healthy silicon launch;
  * BENCH lines' ledger_rollup block reports the backend mix;
  * /debug/launches serves records + rollup + watchdog + hbm;
  * tools/check_ledger.py (dispatch-site lint + overhead budget) is
    clean on this tree.
"""

import asyncio
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from tendermint_tpu.crypto.tpu import backend as tb  # noqa: E402
from tendermint_tpu.crypto.tpu import ledger  # noqa: E402
from tendermint_tpu.crypto.tpu import verify as tv  # noqa: E402
from tendermint_tpu.crypto.tpu import watchdog  # noqa: E402

TPU_DEV = "TPU_0(process=0,(0,0,0,0))"
CPU_DEV = "TFRT_CPU_0"


@pytest.fixture(autouse=True)
def _clean_ledger():
    """Every test starts from an empty ring/HBM registry and the
    default watchdog config; process-global state never leaks."""
    cap = ledger.capacity()
    ledger.reset()
    watchdog.configure()
    yield
    ledger.set_capacity(cap)
    ledger.reset()
    watchdog.configure()


def _fake_record(device=TPU_DEV, verdict="ok", workload=None,
                 exec_ms=1.0, **fields):
    ctx = ledger.workload(workload) if workload else None
    if ctx:
        ctx.__enter__()
    try:
        ledger.record(device=device, verdict=verdict,
                      stages_ms={"exec": exec_ms}, **fields)
    finally:
        if ctx:
            ctx.__exit__(None, None, None)


# ------------------------------------------------------------- ring


def test_ring_bounded_and_evictions_counted():
    ledger.set_capacity(16)
    assert ledger.capacity() == 16
    for i in range(20):
        _fake_record(lanes=i)
    recs = ledger.snapshot()
    assert len(recs) == 16
    assert ledger.evicted() == 4
    # bounded ring keeps the NEWEST records
    assert recs[-1]["lanes"] == 19 and recs[0]["lanes"] == 4
    # floor: capacity can't drop below 16
    ledger.set_capacity(1)
    assert ledger.capacity() == 16


def test_workload_tag_scopes_and_default():
    with ledger.workload("probe"):
        _fake_record()
        with ledger.workload("bench"):
            _fake_record()
        _fake_record()
    _fake_record()
    tags = [r["workload"] for r in ledger.snapshot()]
    assert tags == ["probe", "bench", "probe", "consensus"]


def test_record_timestamps_are_completion_stamped():
    # A first launch whose jit compile outlives the watchdog window
    # must still land inside it: wall/mono are stamped at done(), not
    # begin() — a begin-stamped record born outside the window would
    # classify as idle the instant it lands.
    rec = ledger.begin("general")
    rec.mono = rec.wall = -1e9  # pretend begin() was eons ago
    rec.device = TPU_DEV
    rec.verdict = "ok"
    rec.done()
    r = ledger.snapshot()[-1]
    assert r["mono"] > 0 and r["wall"] > 0
    watchdog.configure(backend="tpu")
    assert watchdog.classify()["launches_in_window"] == 1

    # …except when a caller pins the stamps (idle-window tests, replay)
    ledger.record(device=TPU_DEV, verdict="ok", mono=-1e9)
    assert ledger.snapshot()[-1]["mono"] == -1e9


def test_snapshot_filters_and_rollup_shape():
    _fake_record(workload="probe", lanes=8, bytes_h2d=100)
    _fake_record(workload="probe", lanes=8, bytes_h2d=100,
                 device=CPU_DEV)
    _fake_record(lanes=3, verdict="invalid")
    assert len(ledger.snapshot(workload="probe")) == 2
    roll = ledger.rollup()
    assert roll["records"] == 3 and roll["capacity"] >= 16
    probe = roll["workloads"]["probe"]
    assert probe["launches"] == 2 and probe["lanes"] == 16
    assert probe["bytes_h2d"] == 200
    # backend mix: one silicon, one CPU landing
    assert probe["backends"] == {"tpu": 1, "cpu-fallback": 1}
    assert probe["exec_ms_p50"] > 0
    cons = roll["workloads"]["consensus"]
    assert cons["verdicts"] == {"invalid": 1}


# --------------------------------------------------- dispatch sites


def _fake_btab():
    return np.zeros((64, 8), np.uint8)


def test_general_kernel_chunks_record(monkeypatch):
    """verify.verify_batch: one record per chunk launch, with lanes,
    bucket capacity, compile hit/miss, byte counts and pack/dispatch/
    readback stages."""
    monkeypatch.setattr(tv, "_mesh", lambda: None)
    monkeypatch.setattr(tv, "b_comb_tables", _fake_btab)
    monkeypatch.setattr(
        tv, "_kernel",
        lambda: lambda btab, **packed: np.ones(
            packed["s_ok"].shape[0] if "s_ok" in packed
            else len(next(iter(packed.values()))), bool))
    pubs = [bytes(32)] * 3
    msgs = [b"m%d" % i for i in range(3)]
    sigs = [bytes(64)] * 3
    with ledger.workload("fastsync"):
        out = tv.verify_batch(pubs, msgs, sigs)
    assert out.shape == (3,) and out.all()
    recs = ledger.snapshot()
    assert len(recs) == 1
    r = recs[0]
    assert r["kernel"] == "general" and r["workload"] == "fastsync"
    assert r["lanes"] == 3 and r["capacity"] >= 3
    assert r["occupancy"] == round(3 / r["capacity"], 4)
    assert r["compile_cache"] in ("hit", "miss")
    assert r["bytes_h2d"] > 0 and r["bytes_d2h"] > 0
    assert r["verdict"] == "ok" and r["ok_lanes"] == 3
    for stage in ("pack", "dispatch", "readback"):
        assert stage in r["stages_ms"]


def test_general_kernel_raise_records_and_propagates(monkeypatch):
    monkeypatch.setattr(tv, "_mesh", lambda: None)
    monkeypatch.setattr(tv, "b_comb_tables", _fake_btab)

    def boom():
        raise RuntimeError("device lost")

    monkeypatch.setattr(tv, "_kernel", boom)
    with pytest.raises(RuntimeError):
        tv.verify_batch([bytes(32)], [b"m"], [bytes(64)])
    r = ledger.snapshot()[-1]
    assert r["verdict"] == "raised"
    assert "device lost" in r["error"]


def test_expanded_traced_verify_records():
    """ExpandedKeys._traced_verify emits one record per launch (fake
    prepare/launch closures — no table build)."""
    from tendermint_tpu.crypto.tpu.expanded import ExpandedKeys

    ek = object.__new__(ExpandedKeys)
    ek.sharded = False

    def prepare():
        return (np.zeros((4, 2), np.uint8),), None, np.ones(2, bool)

    def launch(arg, in_order):
        return np.ones(4, bool)

    with ledger.workload("light"):
        out = ek._traced_verify(2, "expanded", prepare, launch)
    assert out.shape == (2,) and out.all()
    r = ledger.snapshot()[-1]
    assert r["kernel"] == "expanded" and r["workload"] == "light"
    assert r["lanes"] == 2 and r["capacity"] == 4
    assert r["bytes_h2d"] == 8 and r["bytes_d2h"] == 4
    assert r["verdict"] == "ok" and r["rows"] == "gathered"
    for stage in ("pack", "dispatch", "readback"):
        assert stage in r["stages_ms"]


def test_arena_delta_bytes_and_lane_accounting(monkeypatch):
    """ResidentArena.launch H2D bytes are the DELTA staged since the
    last launch — splice payloads + the per-launch templates — and
    lane counts track splice/deactivate, byte-exact."""
    from tendermint_tpu.crypto.tpu import resident as rs

    monkeypatch.setattr(tv, "b_comb_tables", _fake_btab)
    arena = rs.ResidentArena(8)
    cap = arena.capacity  # rounds up to the minimum kernel bucket
    monkeypatch.setattr(
        rs, "_arena_kernel",
        lambda width: lambda *a, **k: np.ones(cap, bool))
    template_bytes = int(arena.pre.nbytes + arena.suf.nbytes
                         + arena.pre_len.nbytes + arena.suf_len.nbytes)

    k = 3
    up0 = arena.reupload_bytes
    arena.splice(
        [1, 2, 3], np.zeros((k, 64), np.uint8),
        np.zeros((k, rs.PATCH_W), np.uint8), np.zeros(k, np.int32),
        np.zeros(k, np.int32), np.ones(k, np.int32))
    splice_bytes = arena.reupload_bytes - up0
    assert splice_bytes > 0

    arena.launch()
    r1 = ledger.snapshot()[-1]
    assert r1["kernel"] == "resident"
    assert r1["lanes"] == 1 + k  # sentinel + spliced lanes
    assert r1["capacity"] == cap
    assert r1["bytes_h2d"] == splice_bytes + template_bytes
    assert r1["verdict"] == "ok" and r1["ok_lanes"] == cap
    assert r1["bytes_d2h"] == cap  # (capacity,) bool verdicts

    # steady state: nothing spliced since -> templates only
    arena.launch()
    r2 = ledger.snapshot()[-1]
    assert r2["bytes_h2d"] == template_bytes
    assert r2["compile_cache"] == "hit"

    arena.deactivate_all()
    arena.launch()
    assert ledger.snapshot()[-1]["lanes"] == 1  # sentinel only

    # sentinel failure is its own verdict
    monkeypatch.setattr(
        rs, "_arena_kernel",
        lambda width: lambda *a, **k: np.zeros(cap, bool))
    arena.launch()
    assert ledger.snapshot()[-1]["verdict"] == "sentinel_failed"

    # construction registered the arena's HBM footprint
    hbm = ledger.hbm_snapshot()
    assert any("arena" in kinds for kinds in hbm.values())


def test_mesh_arena_records_shard_distribution(monkeypatch):
    """MeshResidentArena.launch: one record per mesh launch with the
    per-shard lane distribution, n_devices and per-device delta
    bytes (conftest forces the 8-device host mesh)."""
    from tendermint_tpu.crypto.tpu import resident as rs

    mesh = tv._mesh()
    if mesh is None:
        pytest.skip("no device mesh in this environment")
    monkeypatch.setattr(tv, "b_comb_tables", _fake_btab)
    arena = rs.MeshResidentArena(65, mesh=mesh)
    d_n = arena.n_shards
    monkeypatch.setattr(
        rs, "_mesh_arena_kernel",
        lambda width: lambda *a, **k: np.ones(
            (d_n, arena.shard_capacity), bool))
    template_bytes = int(arena.pre.nbytes + arena.suf.nbytes
                         + arena.pre_len.nbytes
                         + arena.suf_len.nbytes) * d_n

    with ledger.workload("speculation"):
        arena.launch()
    r = ledger.snapshot()[-1]
    assert r["kernel"] == "resident_mesh"
    assert r["workload"] == "speculation"
    assert r["n_devices"] == d_n
    assert r["shard_lanes"] == [arena.shard_capacity] * d_n
    assert r["lanes"] == d_n  # one sentinel per shard, nothing spliced
    assert r["bytes_h2d"] == template_bytes  # replicated per device
    assert r["verdict"] == "ok"

    # every shard registered its HBM slice
    hbm = ledger.hbm_snapshot()
    shard_devs = [d for d, kinds in hbm.items() if "arena_shard" in kinds]
    assert len(shard_devs) == d_n


def test_sr25519_dispatch_site_records(monkeypatch):
    from tendermint_tpu.crypto.tpu import sr_verify as sr

    monkeypatch.setattr(tv, "_mesh", lambda: None)
    monkeypatch.setattr(tv, "b_comb_tables", _fake_btab)
    monkeypatch.setattr(
        sr, "_kernel",
        lambda: lambda btab, **args: np.ones(args["s_ok"].shape[0],
                                             bool))
    pubs = [bytes(32)] * 2
    msgs = [b"sr-msg"] * 2
    sigs = [bytes(63) + b"\x80"] * 2  # marker bit set
    with ledger.workload("admission"):
        out = sr.verify_batch_sr(pubs, msgs, sigs)
    assert out.shape == (2,) and out.all()
    r = ledger.snapshot()[-1]
    assert r["kernel"] == "sr25519" and r["workload"] == "admission"
    assert r["lanes"] == 2 and r["capacity"] >= 2
    assert r["bytes_h2d"] > 0
    for stage in ("pack", "dispatch", "readback"):
        assert stage in r["stages_ms"]


# ---------------------------------------------------------- watchdog


def test_backend_classification_helper():
    assert tb.backend_label(TPU_DEV) == "tpu"
    assert tb.backend_label(CPU_DEV) == "cpu-fallback"
    assert tb.effective_state_of(TPU_DEV) == "tpu"
    assert tb.effective_state_of(CPU_DEV) == "cpu_fallback"
    # the misrepresentation check bench_trend delegates to
    backend, problems = tb.classify_stamps("tpu", False, CPU_DEV)
    assert backend == "cpu_fallback" and problems
    backend, problems = tb.classify_stamps("tpu", False, TPU_DEV)
    assert backend == "silicon" and not problems


def test_watchdog_degrades_within_one_launch_and_recovers():
    """The acceptance path: crypto.backend=tpu configured, the device
    path lands on CPU -> /status device check degrades with
    effective_backend=cpu_fallback after ONE launch, the one-hot gauge
    flips, and one healthy silicon launch recovers it."""
    from tendermint_tpu.crypto import batch as cbatch
    from tendermint_tpu.libs.debugsrv import HealthMonitor
    from tendermint_tpu.libs.metrics import tpu_metrics

    cbatch.reset_breakers()
    watchdog.configure("tpu", 60.0)
    mon = HealthMonitor()

    # empty ledger: unknown, never degraded (fresh boot)
    dv = mon.status()["checks"]["device"]
    assert dv["status"] == "ok"
    assert dv["effective_backend"] == "unknown"

    # ONE launch landing on CPU (a chip was promised)
    _fake_record(device=CPU_DEV)
    dv = mon.status()["checks"]["device"]
    assert dv["status"] == "degraded"
    assert dv["effective_backend"] == "cpu_fallback"
    assert dv["configured_backend"] == "tpu"
    assert "cpu_fallback" in dv["detail"]
    assert dv["last_device_launch_age_s"] is not None
    assert dv["launches_in_window"] == 1
    g = tpu_metrics().effective_backend
    assert g.value(backend="cpu_fallback") == 1
    assert g.value(backend="tpu") == 0

    # raising launches are also cpu_fallback evidence
    with pytest.raises(ValueError):
        with ledger.launch("general"):
            raise ValueError("XLA dead")
    assert mon.status()["checks"]["device"]["status"] == "degraded"

    # ONE healthy silicon launch (the breaker probe shape) recovers
    _fake_record(device=TPU_DEV, workload="probe")
    dv = mon.status()["checks"]["device"]
    assert dv["status"] == "ok"
    assert dv["effective_backend"] == "tpu"
    assert g.value(backend="tpu") == 1
    assert g.value(backend="cpu_fallback") == 0


def test_watchdog_never_degrades_without_tpu_promise():
    watchdog.configure("auto")
    _fake_record(device=CPU_DEV)
    assert watchdog.verdict()["status"] == "ok"
    watchdog.configure("cpu")
    assert watchdog.verdict()["status"] == "ok"
    watchdog.configure("tpu")
    assert watchdog.verdict()["status"] == "degraded"


def test_watchdog_exec_drift_degrades(monkeypatch):
    monkeypatch.setenv("TM_TPU_SILICON_BASELINE_MS", "1.0")
    watchdog.configure("tpu")
    _fake_record(device=TPU_DEV, exec_ms=1.5)
    assert watchdog.verdict()["status"] == "ok"
    ledger.reset()
    _fake_record(device=TPU_DEV, exec_ms=10.0)
    v = watchdog.verdict()
    assert v["status"] == "degraded" and "drifted" in v["reason"]


def test_watchdog_hbm_budget(monkeypatch):
    ledger.register_hbm("comb_tables", TPU_DEV, 17 * 1024**3)
    v = watchdog.verdict()  # over budget degrades even on "auto"
    assert v["status"] == "degraded" and "HBM over budget" in v["reason"]
    ledger.register_hbm("comb_tables", TPU_DEV, 0)  # release
    assert watchdog.verdict()["status"] == "ok"
    assert ledger.hbm_device_totals() == {}


def test_watchdog_idle_window():
    watchdog.configure("tpu", 60.0)
    rec = {"mono": -1e9, "device": TPU_DEV, "verdict": "ok",
           "stages_ms": {}}
    cls = watchdog.classify([rec])
    assert cls["effective_backend"] == "idle"


# ----------------------------------------------------- export surfaces


def test_bench_line_rollup_reports_backend_mix():
    import bench

    with ledger.workload("bench"):
        _fake_record(device=TPU_DEV, lanes=1024)
        _fake_record(device=CPU_DEV, lanes=1024)
    roll = bench.ledger_rollup()
    assert roll["bench"]["launches"] == 2
    assert roll["bench"]["backends"] == {"tpu": 1, "cpu-fallback": 1}
    # the block is what bench.py embeds: JSON-serializable as-is
    json.dumps(roll)


def test_debug_launches_endpoint():
    from tendermint_tpu.libs.debugsrv import DebugServer

    _fake_record(workload="probe", lanes=8)
    _fake_record(lanes=4)
    ledger.register_hbm("arena", TPU_DEV, 4096)

    async def run():
        srv = DebugServer()
        port = await srv.start()

        async def get(path):
            r, w = await asyncio.open_connection("127.0.0.1", port)
            w.write(f"GET {path} HTTP/1.0\r\n\r\n".encode())
            await w.drain()
            data = await r.read()
            w.close()
            return data

        raw = await get("/debug/launches")
        head, _, body = raw.partition(b"\r\n\r\n")
        assert b"application/json" in head
        doc = json.loads(body)
        assert len(doc["records"]) == 2
        assert doc["rollup"]["workloads"]["probe"]["launches"] == 1
        assert doc["watchdog"]["effective_backend"] == "tpu"
        assert doc["hbm"][TPU_DEV]["arena"] == 4096

        raw = await get("/debug/launches?workload=probe")
        doc = json.loads(raw.partition(b"\r\n\r\n")[2])
        assert [r["workload"] for r in doc["records"]] == ["probe"]
        srv.close()

    asyncio.run(run())


def test_launch_ledger_analyzer_tool(tmp_path, capsys):
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    from tools import launch_ledger as tool

    _fake_record(workload="probe", lanes=8, bytes_h2d=100)
    _fake_record(device=CPU_DEV, lanes=4)
    payload = {"records": ledger.snapshot(), "rollup": ledger.rollup(),
               "watchdog": watchdog.classify(),
               "hbm": ledger.hbm_snapshot()}
    p = tmp_path / "launches.json"
    p.write_text(json.dumps(payload))
    assert tool.main([str(p)]) == 0
    out = capsys.readouterr().out
    line = next(ln for ln in out.splitlines()
                if ln.startswith("LEDGER_SUMMARY "))
    summary = json.loads(line.split(" ", 1)[1])
    assert summary["launches"] == 2
    assert summary["backends"] == {"tpu": 1, "cpu-fallback": 1}
    assert summary["effective_backend"] == "tpu"


def test_config_crypto_section_roundtrip(tmp_path):
    from tendermint_tpu.config import Config, CryptoConfig

    cfg = Config()
    cfg.crypto.backend = "tpu"
    cfg.crypto.watchdog_window_s = 12.5
    cfg.crypto.ledger_capacity = 64
    path = tmp_path / "config.toml"
    cfg.save(str(path))
    loaded = Config.load(str(path))
    assert loaded.crypto.backend == "tpu"
    assert loaded.crypto.watchdog_window_s == 12.5
    assert loaded.crypto.ledger_capacity == 64
    with pytest.raises(ValueError):
        CryptoConfig(backend="gpu").validate_basic()
    with pytest.raises(ValueError):
        CryptoConfig(ledger_capacity=2).validate_basic()


# ------------------------------------------------------------- lints


def test_check_ledger_lint_clean():
    """Dispatch-site catalog, workload tag set, and docs all in sync;
    per-record overhead inside the shared span budget."""
    from tools.check_ledger import collect_problems, measure_overhead
    from tools.check_spans import ENABLED_BUDGET_S

    assert collect_problems() == []
    assert measure_overhead(n=2000) <= ENABLED_BUDGET_S
