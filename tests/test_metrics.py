"""Metrics registry + debug/pprof server + /metrics RPC route."""

import asyncio

from tendermint_tpu.libs.metrics import (
    DEFAULT, Counter, Gauge, Histogram, Registry,
    consensus_metrics, crypto_metrics,
)


def test_counter_gauge_histogram_render():
    reg = Registry()
    c = reg.counter("reqs_total", "Requests.", "test")
    c.inc()
    c.inc(2, code="200")
    g = reg.gauge("height", "Height.", "test")
    g.set(42)
    h = reg.histogram("lat", "Latency.", "test", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.render_text()
    assert "# TYPE test_reqs_total counter" in text
    assert 'test_reqs_total{code="200"} 2' in text
    assert "test_height 42" in text
    assert 'test_lat_bucket{le="0.1"} 1' in text
    assert 'test_lat_bucket{le="+Inf"} 3' in text
    assert "test_lat_count 3" in text


def test_histogram_timer():
    reg = Registry()
    h = reg.histogram("t", "T.", "x")
    with h.time():
        pass
    assert h.count == 1 and h.sum >= 0


def test_module_singletons_registered():
    cm = consensus_metrics()
    assert consensus_metrics() is cm
    cm.height.set(7)
    km = crypto_metrics()
    before = km.batch_lanes.value(backend="tpu")
    km.batch_lanes.inc(128, backend="tpu")
    text = DEFAULT.render_text()
    assert "consensus_height 7" in text
    from tendermint_tpu.libs.metrics import _fmt_value

    assert (f'crypto_batch_lanes_total{{backend="tpu"}} '
            f'{_fmt_value(before + 128)}') in text
    # The registry carries a healthy metric surface (>= 15 metrics).
    import tendermint_tpu.libs.metrics as M

    M.p2p_metrics()
    M.mempool_metrics()
    M.state_metrics()
    names = {m.name for m in DEFAULT._metrics}
    assert len(names) >= 15, sorted(names)


def test_batch_verifier_records_metrics():
    from tendermint_tpu.crypto.batch import BatchVerifier
    from tendermint_tpu.crypto.ed25519 import Ed25519PrivKey

    km = crypto_metrics()
    before = km.batch_lanes.value(backend="host")
    bad_before = km.invalid_sigs.value()
    bv = BatchVerifier()
    k = Ed25519PrivKey.from_secret(b"m")
    bv.add(k.pub_key(), b"msg", k.sign(b"msg"))
    bv.add(k.pub_key(), b"other", k.sign(b"msg"))
    ok, verdicts = bv.verify()
    assert not ok and verdicts.tolist() == [True, False]
    assert km.batch_lanes.value(backend="host") == before + 2
    assert km.invalid_sigs.value() == bad_before + 1


def test_debug_server_routes():
    from tendermint_tpu.libs.debugsrv import DebugServer

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

        idx = await get("/debug/pprof/")
        assert b"pprof endpoints" in idx
        goro = await get("/debug/pprof/goroutine")
        assert b"asyncio tasks" in goro
        heap = await get("/debug/pprof/heap?seconds=0.1")
        assert b"traced current=" in heap
        # REGRESSION GUARD: the heap route must not leave tracemalloc
        # running — it slows the whole process 3-4x (one debug-dump
        # poll used to permanently degrade the node AND every
        # kernel-compile test that ran after this one in the suite).
        import tracemalloc

        assert not tracemalloc.is_tracing()
        met = await get("/metrics")
        assert b"# TYPE" in met
        srv.close()

    asyncio.run(run())


def test_rpc_metrics_route():
    from tendermint_tpu.rpc.jsonrpc import JSONRPCServer

    async def run():
        srv = JSONRPCServer(routes={})
        port = await srv.listen("127.0.0.1", 0)

        r, w = await asyncio.open_connection("127.0.0.1", port)
        w.write(b"GET /metrics HTTP/1.1\r\nConnection: close\r\n\r\n")
        await w.drain()
        data = await r.read()
        w.close()
        assert b"200 OK" in data and b"# TYPE" in data

        srv.close()

    asyncio.run(run())


def test_label_value_escaping():
    """Backslash, double-quote and newline in label values must be
    escaped per the exposition format — raw emission produces
    unparseable output for labels like peer addresses."""
    reg = Registry()
    c = reg.counter("conns_total", "Conns.", "test")
    c.inc(1, addr='tcp://10.0.0.1:26656/"quoted"\\path\nline2')
    text = reg.render_text()
    assert ('test_conns_total{addr="tcp://10.0.0.1:26656/'
            '\\"quoted\\"\\\\path\\nline2"} 1') in text
    # help text escapes newline/backslash too
    h = reg.counter("x_total", "line1\nline2\\tail", "test")
    assert "# HELP test_x_total line1\\nline2\\\\tail" in h.render()[0]


def test_labelled_histogram_render_and_cumulative_buckets():
    reg = Registry()
    h = reg.histogram("lat", "Latency.", "test", buckets=(0.1, 1.0))
    h.observe(0.05, conn="consensus")
    h.observe(0.5, conn="consensus")
    h.observe(5.0, conn="query")
    bound = h.labels(conn="consensus")
    bound.observe(0.07)
    text = reg.render_text()
    # cumulative within each labelset, le merged with the labels
    assert 'test_lat_bucket{conn="consensus",le="0.1"} 2' in text
    assert 'test_lat_bucket{conn="consensus",le="1"} 3' in text
    assert 'test_lat_bucket{conn="consensus",le="+Inf"} 3' in text
    assert 'test_lat_count{conn="consensus"} 3' in text
    assert 'test_lat_bucket{conn="query",le="0.1"} 0' in text
    assert 'test_lat_bucket{conn="query",le="+Inf"} 1' in text
    assert h.count == 4
    # an unobserved histogram still renders a zero series (family
    # visibility on first scrape)
    h2 = reg.histogram("idle", "Idle.", "test", buckets=(1.0,))
    out = "\n".join(h2.render())
    assert 'test_idle_bucket{le="+Inf"} 0' in out
    assert "test_idle_count 0" in out


def test_histogram_concurrent_observe_render_consistent():
    """Executor threads observe while the event loop renders: every
    rendered snapshot must keep cumulative buckets monotone and
    +Inf == _count (they derive from one snapshot of the bucket
    array)."""
    import re
    import threading

    reg = Registry()
    h = reg.histogram("t", "T.", "x", buckets=(0.5,))
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            h.observe(0.1)
            h.observe(0.9)

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        for _ in range(200):
            text = reg.render_text()
            buckets = [int(m) for m in re.findall(
                r'x_t_bucket{le="[^"]+"} (\d+)', text)]
            count = int(re.search(r"x_t_count (\d+)", text).group(1))
            assert buckets == sorted(buckets), "cumulative not monotone"
            assert buckets[-1] == count, "+Inf bucket != _count"
    finally:
        stop.set()
        for t in threads:
            t.join()


def test_tracing_metrics_bridge():
    """A span close on the global TRACER must populate
    tracing_span_seconds{kind=...} — the device pipeline's stage kinds
    like every other — with no extra instrumentation call site."""
    from tendermint_tpu.libs import tracing
    from tendermint_tpu.libs.metrics import tracing_metrics

    sink_hist = tracing_metrics().span_seconds
    for kind, attrs in ((tracing.CRYPTO_PACK, {"lanes": 4}),
                        (tracing.WAL_FSYNC, {})):
        before = sink_hist.count
        with tracing.TRACER.span(kind, **attrs):
            pass
        assert sink_hist.count == before + 1
    text = DEFAULT.render_text()
    assert 'tracing_span_seconds_bucket{kind="wal.fsync",le="+Inf"}' \
        in text
    assert 'tracing_span_seconds_bucket{kind="crypto.pack",le="+Inf"}' \
        in text
    # the stage kinds have no series of their own any more
    assert "tpu_pack_" not in text and "tpu_readback_" not in text

    # private tracers have no sink: a test Tracer must not feed the
    # process registry
    t = tracing.Tracer(capacity=8)
    before = sink_hist.count
    with t.span(tracing.CRYPTO_PACK, lanes=1):
        pass
    assert sink_hist.count == before


def test_metrics_and_status_endpoints_end_to_end():
    """GET /metrics on a DebugServer exposes the full catalog (>= 8
    namespaces, materialized on scrape) and GET /status returns the
    machine-readable health verdict."""
    import json

    from tendermint_tpu.libs.debugsrv import DebugServer

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

        met = await get("/metrics")
        head, _, body = met.partition(b"\r\n\r\n")
        text = body.decode()
        for ns in ("consensus", "mempool", "p2p", "blockchain",
                   "statesync", "evidence", "state", "abci", "tpu"):
            assert f"# TYPE {ns}_" in text, f"namespace {ns} missing"

        raw = await get("/status")
        head, _, body = raw.partition(b"\r\n\r\n")
        assert b"application/json" in head
        doc = json.loads(body)
        assert doc["status"] in ("ok", "degraded", "failing")
        for check in ("consensus", "p2p", "mempool", "device"):
            assert doc["checks"][check]["status"] in (
                "ok", "degraded", "failing")
        # no node attached, nothing committed: consensus can't be "ok"
        assert doc["checks"]["consensus"]["height"] == \
            int(consensus_metrics().height.value())
        srv.close()

    asyncio.run(run())


def test_abci_proxy_method_latency():
    """AppConns wraps every connection's deliver() with the
    per-(connection, method) latency histogram."""
    from tendermint_tpu.abci import types as abci_t
    from tendermint_tpu.abci.client import ClientCreator
    from tendermint_tpu.abci.kvstore import KVStoreApp
    from tendermint_tpu.libs.metrics import abci_metrics
    from tendermint_tpu.proxy import AppConns

    hist = abci_metrics().method_seconds

    async def run():
        conns = AppConns(ClientCreator(app=KVStoreApp()))
        await conns.start()
        try:
            await conns.query.echo("hi")
            await conns.mempool.check_tx(
                abci_t.RequestCheckTx(tx=b"k=v"))
        finally:
            await conns.stop()

    q_bound = hist.labels(connection="query", method="echo")
    m_bound = hist.labels(connection="mempool", method="check_tx")
    q0 = sum(q_bound._series.counts)
    m0 = sum(m_bound._series.counts)
    asyncio.run(run())
    assert sum(q_bound._series.counts) == q0 + 1
    assert sum(m_bound._series.counts) == m0 + 1
    text = DEFAULT.render_text()
    assert ('abci_connection_method_seconds_bucket{connection="query",'
            'le="+Inf",method="echo"}') in text


def test_check_metrics_lint_and_docs_sync():
    from tools.check_metrics import collect_problems

    assert collect_problems() == []


def test_metrics_snapshot_delta():
    from tendermint_tpu.libs import metrics as M

    reg = Registry()
    c = reg.counter("ops_total", "Ops.", "test")
    h = reg.histogram("lat", "Lat.", "test", buckets=(0.1, 1.0, 10.0))
    c.inc(3, kind="a")
    h.observe(0.05)
    before = M.snapshot(reg)
    c.inc(2, kind="a")
    c.inc(1, kind="b")
    h.observe(0.5)
    h.observe(0.6)
    d = M.delta(before, M.snapshot(reg))
    assert d['test_ops_total{kind="a"}'] == 2
    assert d['test_ops_total{kind="b"}'] == 1
    hd = d["test_lat"]
    assert hd["count"] == 2
    assert abs(hd["sum"] - 1.1) < 1e-6
    assert 0.1 <= hd["p50"] <= 1.0  # both new observes in (0.1, 1.0]


def test_node_metrics_provider_gating():
    from tendermint_tpu.config import InstrumentationConfig
    from tendermint_tpu.libs.metrics import NodeMetrics, metrics_provider

    on = metrics_provider(InstrumentationConfig(prometheus=True))
    off = metrics_provider(InstrumentationConfig(prometheus=False))
    assert isinstance(on("chain-a"), NodeMetrics)
    assert off("chain-a") is None


def test_reference_catalog_metrics_present():
    """Every metric in the reference's docs/nodes/metrics.md catalog
    has an equivalent in our registries (naming: <ns>_<name>)."""
    from tendermint_tpu.libs.metrics import (
        DEFAULT, consensus_metrics, mempool_metrics, p2p_metrics,
        state_metrics,
    )

    consensus_metrics(), mempool_metrics(), p2p_metrics(), state_metrics()
    text = DEFAULT.render_text()
    for want in (
        "consensus_height", "consensus_validators",
        "consensus_validators_power", "consensus_validator_power",
        "consensus_validator_last_signed_height",
        "consensus_validator_missed_blocks",
        "consensus_missing_validators",
        "consensus_missing_validators_power",
        "consensus_byzantine_validators",
        "consensus_byzantine_validators_power",
        "consensus_block_interval_seconds", "consensus_rounds",
        "consensus_num_txs", "consensus_total_txs",
        "consensus_fast_syncing", "consensus_state_syncing",
        "consensus_block_size_bytes",
        "p2p_peers", "p2p_peer_receive_bytes_total",
        "p2p_peer_send_bytes_total", "p2p_pending_send_bytes",
        "mempool_size", "mempool_tx_size_bytes", "mempool_failed_txs",
        "mempool_recheck_times",
        "state_block_processing_seconds",
    ):
        assert want in text, f"{want} missing from /metrics"
