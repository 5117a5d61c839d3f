"""Span tracer (libs/tracing.py): nesting across the event-loop /
executor boundary, ring-buffer eviction, Chrome trace-event export,
the consensus-height timeline + /debug/trace endpoint, the
check_spans lint/overhead budgets — plus regression tests for the
round-5 findings fixed alongside (WAL repair re-stat race, BlockID
IsZero canonicalization, PEX flood-strike decay)."""

from __future__ import annotations

import asyncio
import json
import threading
import time
import zlib

import pytest

from tendermint_tpu.libs import tracing
from tendermint_tpu.libs.tracing import TRACER, Tracer, chrome_trace

# -------------------------------------------------------------- core tracer


def test_span_nesting_and_parent_links():
    t = Tracer(capacity=64)
    with t.span(tracing.CONSENSUS_HEIGHT, height=7) as root:
        with t.span(tracing.CONSENSUS_PROPOSE) as child:
            assert t.current() is child
        assert t.current() is root
    assert t.current() is None
    recs = {r[0]: r for r in t.snapshot()}
    assert recs[tracing.CONSENSUS_PROPOSE][2] == root.span_id
    assert recs[tracing.CONSENSUS_HEIGHT][2] == 0
    assert recs[tracing.CONSENSUS_HEIGHT][6] == {"height": 7}
    # children seal before parents; durations nest
    assert recs[tracing.CONSENSUS_PROPOSE][5] <= \
        recs[tracing.CONSENSUS_HEIGHT][5]


def test_span_nesting_across_executor_handoff():
    """run_in_executor does not carry the caller's Context; the
    explicit TRACER.wrap handoff must."""
    t = Tracer(capacity=64)
    seen = {}

    async def go():
        loop = asyncio.get_running_loop()

        def work():
            cur = t.current()
            seen["inside"] = cur.span_id if cur else 0
            with t.span(tracing.CRYPTO_BATCH, lanes=3):
                pass

        def bare():
            cur = t.current()
            seen["bare"] = cur.span_id if cur else 0

        with t.span(tracing.CONSENSUS_VOTE_BATCH, lanes=3) as parent:
            seen["parent"] = parent.span_id
            await loop.run_in_executor(None, t.wrap(work))
            await loop.run_in_executor(None, bare)

    asyncio.run(go())
    assert seen["inside"] == seen["parent"] != 0
    assert seen["bare"] == 0  # no handoff -> no inherited span
    recs = {r[0]: r for r in t.snapshot()}
    batch = recs[tracing.CRYPTO_BATCH]
    assert batch[2] == seen["parent"]          # cross-thread lineage
    assert batch[3] != recs[tracing.CONSENSUS_VOTE_BATCH][3]  # other thread


def test_ring_buffer_eviction_under_overflow():
    t = Tracer(capacity=8)
    for i in range(50):
        with t.span(tracing.CRYPTO_PACK, lanes=i):
            pass
    assert len(t) == 8
    lanes = [r[6]["lanes"] for r in t.snapshot()]
    assert lanes == list(range(42, 50))  # oldest evicted, order kept


def test_ring_eviction_counts_dropped_spans():
    """Evictions are COUNTED, not silent: `dropped` says how many
    spans `/debug/trace` can no longer show, the drop sink bridges the
    count to tracing_spans_dropped_total, and clear() resets it."""
    t = Tracer(capacity=8)
    sunk = []
    t.set_drop_sink(sunk.append)
    for i in range(50):
        with t.span(tracing.CRYPTO_PACK, lanes=i):
            pass
    assert t.dropped == 42
    assert sum(sunk) == 42
    # a raising sink never breaks the span path
    t.set_drop_sink(lambda n: 1 / 0)
    with t.span(tracing.CRYPTO_PACK, lanes=99):
        pass
    assert t.dropped == 43
    t.clear()
    assert t.dropped == 0 and len(t) == 0


def test_origin_tag_codec_roundtrip_and_garbage_tolerance():
    tag = tracing.encode_origin(12345, 3, "sim2", span_id=0xDEADBEEF)
    dec = tracing.decode_origin(tag)
    assert dec == tracing.OriginTag(12345, 3, "sim2", 0xDEADBEEF)
    # never raises on garbage: truncated, empty, wrong version
    assert tracing.decode_origin(b"") is None
    assert tracing.decode_origin(b"\x01\x02") is None
    assert tracing.decode_origin(b"\xff" + tag[1:]) is None
    assert tracing.decode_origin(tag[:5]) is None
    # node labels cap at 64 bytes on the wire
    long = tracing.decode_origin(tracing.encode_origin(1, 0, "x" * 200))
    assert len(long.node) == 64


def test_origin_stamp_and_rehydrate_attach_to_current_span():
    """origin_stamp captures the CURRENT span's id at send; on the
    receiver rehydrate_origin folds the decoded tag into the current
    (recv) span's attrs. No current span -> stamp still encodes
    (span_id 0) and rehydrate is a no-op, never an error."""
    t = Tracer(capacity=32)
    tok = tracing._CURRENT.set(None)
    try:
        with t.span(tracing.CONSENSUS_PROPOSE, height=9) as send_sp:
            tag = tracing.origin_stamp("val1", 9, 2)
        dec = tracing.decode_origin(tag)
        assert dec.node == "val1" and dec.height == 9 and dec.round == 2
        assert dec.span_id == send_sp.span_id

        with t.span(tracing.P2P_RECV_MSG, chan=0x21):
            tracing.rehydrate_origin(tag)
        recv = t.snapshot()[-1]
        assert recv[6]["origin_node"] == "val1"
        assert recv[6]["origin_height"] == 9
        assert recv[6]["origin_round"] == 2
        assert recv[6]["origin_span"] == send_sp.span_id

        # outside any span: no crash, nothing recorded
        bare = tracing.origin_stamp("val1", 10, 0)
        assert tracing.decode_origin(bare).span_id == 0
        tracing.rehydrate_origin(bare)
        tracing.rehydrate_origin(b"not-a-tag")
    finally:
        tracing._CURRENT.reset(tok)


def test_disabled_tracer_records_nothing():
    t = Tracer(capacity=8, enabled=False)
    with t.span(tracing.CRYPTO_PACK, lanes=1) as sp:
        assert sp is tracing.NOOP_SPAN
        assert t.current() is None
    assert len(t) == 0
    assert t.begin(tracing.CRYPTO_PACK) is tracing.NOOP_SPAN


def test_unregistered_kind_rejected():
    t = Tracer(capacity=8)
    with pytest.raises(ValueError, match="unregistered span kind"):
        t.begin("adhoc.kind")


def test_chrome_trace_json_schema_roundtrip():
    t = Tracer(capacity=64)
    with t.span(tracing.CRYPTO_VERIFY, lanes=4, backend="general"):
        with t.span(tracing.CRYPTO_PACK, lanes=4):
            pass
    doc = json.loads(json.dumps(chrome_trace(t.snapshot())))
    evs = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms" and len(evs) == 2
    for e in evs:
        assert e["ph"] == "X"
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert e["name"] in tracing.registered_kinds()
        assert e["cat"] == e["name"].partition(".")[0]
        assert isinstance(e["args"]["span_id"], int)
    pack = next(e for e in evs if e["name"] == tracing.CRYPTO_PACK)
    ver = next(e for e in evs if e["name"] == tracing.CRYPTO_VERIFY)
    assert pack["args"]["parent_id"] == ver["args"]["span_id"]
    assert ver["args"]["backend"] == "general"
    # ts/dur containment (what makes Perfetto render the nesting)
    assert ver["ts"] <= pack["ts"]
    assert pack["ts"] + pack["dur"] <= ver["ts"] + ver["dur"] + 1e-6


def test_stage_rollup_windows_and_prefix():
    t = Tracer(capacity=64)
    for i in range(10):
        with t.span(tracing.CRYPTO_PACK, lanes=i):
            pass
    with t.span(tracing.WAL_FSYNC):
        pass
    roll = t.stage_rollup()
    assert roll[tracing.CRYPTO_PACK]["count"] == 10
    assert 0 <= roll[tracing.CRYPTO_PACK]["p50_ms"] \
        <= roll[tracing.CRYPTO_PACK]["p95_ms"] \
        <= roll[tracing.CRYPTO_PACK]["p99_ms"]
    only_crypto = t.stage_rollup(prefix="crypto.")
    assert tracing.WAL_FSYNC not in only_crypto
    assert only_crypto[tracing.CRYPTO_PACK]["count"] == 10
    assert t.stage_rollup(seconds=3600)[tracing.WAL_FSYNC]["count"] == 1


# ------------------------------------------------- thread-CPU time (cpu_ns)

CPU_KIND = tracing.register_kind("test.cpu_marked", cpu=True)
PLAIN_KIND = tracing.register_kind("test.cpu_unmarked")


def _busy(seconds: float) -> None:
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


# loose on purpose: the suite runs six workers wide
@pytest.mark.parametrize("body,lo,hi", [(_busy, 0.5, 1.0),
                                        (time.sleep, 0.0, 0.2)],
                         ids=["busy", "sleep"])
def test_marked_kind_records_its_threads_cpu_time(body, lo, hi):
    t = Tracer(capacity=8)
    with t.span(CPU_KIND, lanes=3):
        body(0.2)
    (rec,) = t.snapshot()
    dur, attrs = rec[5], rec[6]
    assert attrs["lanes"] == 3
    # no upper bound at dur: a thread clock that ticks (10 ms on the
    # benchmark's host) can read more CPU than the span lasted
    assert attrs["cpu_ns"] >= 0
    assert lo * dur <= attrs["cpu_ns"]
    if hi < 1.0:
        assert attrs["cpu_ns"] < hi * dur
    # the export carries it as it carries any other attribute
    (event,) = chrome_trace(t.snapshot())["traceEvents"]
    assert event["args"]["cpu_ns"] == attrs["cpu_ns"]


def test_unmarked_kind_and_noop_span_record_no_cpu_time():
    t = Tracer(capacity=8)
    with t.span(PLAIN_KIND):
        _busy(0.01)
    with t.span(PLAIN_KIND, lanes=1):
        pass
    assert [r[6] for r in t.snapshot()] == [None, {"lanes": 1}]
    off = Tracer(capacity=8, enabled=False)
    with off.span(CPU_KIND) as span:
        assert span is tracing.NOOP_SPAN
    assert off.snapshot() == []
    # marked are the twelve kinds a reader sums (a read of the thread
    # clock can be a system call): never an `await`-wrapping kind, whose
    # loop-thread CPU is other tasks', and no kind that feeds no metric
    assert {k for k in tracing._CPU_KINDS if not k.startswith("test.")} == {
        tracing.STORE_SAVE_BLOCK, tracing.VALIDATE_BLOCK,
        tracing.VERIFY_WINDOW, tracing.STORE_ENCODE_COMMITS,
        tracing.STORE_ENCODE_PARTS, tracing.VALIDATE_BASIC,
        tracing.VALIDATE_SET_HASHES, tracing.VALIDATE_MEDIAN_TIME,
        tracing.STATE_UPDATE, tracing.VERIFY_COLLECT,
        tracing.VERIFY_SIGN_BATCH, tracing.CRYPTO_PACK}


def test_span_ended_on_another_thread_records_no_cpu_time():
    t = Tracer(capacity=8)
    span = t.begin(CPU_KIND, lanes=2)
    th = threading.Thread(target=span.end)
    th.start()
    th.join(timeout=10)
    assert not th.is_alive()
    (rec,) = t.snapshot()
    assert rec[6] == {"lanes": 2}


# ------------------------------------------- lint + overhead budget (CI gate)


def test_check_spans_lint_and_overhead_budget():
    from tools.check_spans import (
        DISABLED_BUDGET_S, ENABLED_BUDGET_S, find_ad_hoc_spans,
        measure_overhead,
    )

    assert find_ad_hoc_spans() == []
    # the budget is held by a MARKED kind: both thread_time_ns() stamps
    assert tracing.CRYPTO_PACK in tracing._CPU_KINDS
    enabled, disabled = measure_overhead(n=5000)
    assert enabled < ENABLED_BUDGET_S, \
        f"enabled tracer overhead {enabled * 1e6:.1f}us over budget"
    assert disabled < DISABLED_BUDGET_S, \
        f"disabled tracer overhead {disabled * 1e6:.1f}us over budget"


# ------------------------------------- consensus timeline + /debug/trace


def test_consensus_height_timeline_and_trace_endpoint(tmp_path):
    """A committing node must leave a height root span with
    propose/prevote/precommit/commit children, wal.fsync +
    state.apply_block spans, and — after one forced device-path
    batch — a crypto.verify span with pack/dispatch/device_exec/
    readback children; all served as Chrome trace JSON by
    GET /debug/trace."""
    from test_consensus import Node

    from helpers import make_genesis
    from tendermint_tpu.crypto import batch as cbatch
    from tendermint_tpu.crypto.ed25519 import Ed25519PrivKey
    from tendermint_tpu.libs.debugsrv import DebugServer

    TRACER.clear()

    async def go():
        gdoc, pvs = make_genesis(1)
        node = Node(gdoc, pvs[0], tmp_path)
        await node.start()
        srv = DebugServer()
        port = await srv.start()
        try:
            await node.cs.wait_for_height(2, timeout=60)
            # One explicit device-path verify (the 1-validator commits
            # above stay under _DEVICE_THRESHOLD and take the host
            # path). CPU JAX backend; clear any cooldown a previous
            # test's simulated device failure left behind.
            cbatch.reset_breakers()
            bv = cbatch.BatchVerifier(use_device=True)
            for i in range(4):
                k = Ed25519PrivKey.from_secret(b"trace-%d" % i)
                bv.add(k.pub_key(), b"msg-%d" % i, k.sign(b"msg-%d" % i))
            all_ok, _ = bv.verify()
            assert all_ok
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", port)
            writer.write(b"GET /debug/trace?seconds=600 HTTP/1.0\r\n\r\n")
            await writer.drain()
            raw = await reader.read()
            writer.close()
            return raw
        finally:
            srv.close()
            await node.stop()

    raw = asyncio.run(go())
    head, _, body = raw.partition(b"\r\n\r\n")
    assert b"200 OK" in head and b"application/json" in head
    evs = json.loads(body)["traceEvents"]

    def children_of(span_event):
        sid = span_event["args"]["span_id"]
        return {e["name"] for e in evs
                if e["args"].get("parent_id") == sid}

    heights = [e for e in evs if e["name"] == tracing.CONSENSUS_HEIGHT]
    assert heights, "no consensus.height root span"
    steps = {tracing.CONSENSUS_PROPOSE, tracing.CONSENSUS_PREVOTE,
             tracing.CONSENSUS_PRECOMMIT, tracing.CONSENSUS_COMMIT}
    assert any(steps <= children_of(h) for h in heights), \
        "no height span carrying all four step children"
    # update_to_state -> round 0 (the timeout_commit wait) has a span
    # of its own, first child of its height, sealed when propose opens
    waited = [h for h in heights
              if tracing.CONSENSUS_NEW_HEIGHT in children_of(h)]
    assert waited, "no height with a consensus.new_height child"
    for h in waited:
        kids = sorted((e for e in evs
                       if e["args"].get("parent_id") == h["args"]["span_id"]),
                      key=lambda e: e["ts"])
        assert kids[0]["name"] == tracing.CONSENSUS_NEW_HEIGHT
        assert abs(kids[0]["ts"] - h["ts"]) < 1e3   # us: begun together
        if len(kids) > 1:
            assert kids[1]["name"] == tracing.CONSENSUS_PROPOSE
            assert kids[0]["ts"] + kids[0]["dur"] <= kids[1]["ts"] + 1
    applied = [e for e in evs if e["name"] == tracing.STATE_APPLY_BLOCK]
    assert any(children_of(a) >= {
        tracing.STATE_VALIDATE, tracing.STATE_EXEC,
        tracing.STATE_SAVE_RESPONSES, tracing.STATE_UPDATE,
        tracing.STATE_APP_COMMIT, tracing.STATE_SAVE,
        tracing.STATE_EVENTS} for a in applied)
    assert any(e["name"] == tracing.STORE_SAVE_BLOCK for e in evs)
    assert any(e["name"] == tracing.STATE_APPLY_BLOCK for e in evs)
    assert any(e["name"] == tracing.WAL_FSYNC for e in evs)

    verifies = [e for e in evs if e["name"] == tracing.CRYPTO_VERIFY]
    stages = {tracing.CRYPTO_PACK, tracing.CRYPTO_DISPATCH,
              tracing.CRYPTO_DEVICE_EXEC, tracing.CRYPTO_READBACK}
    assert any(stages <= children_of(v) for v in verifies), \
        "no crypto.verify span with all four stage children"
    # the forced batch routed through BatchVerifier: its crypto.batch
    # span must parent the device crypto.verify span
    batches = {e["args"]["span_id"] for e in evs
               if e["name"] == tracing.CRYPTO_BATCH}
    assert any(v["args"].get("parent_id") in batches for v in verifies)


def test_debug_trace_cli(tmp_path):
    """`tendermint-tpu debug trace` writes a Perfetto-loadable file
    from a live debug server."""
    from tendermint_tpu.cmd import main
    from tendermint_tpu.libs.debugsrv import DebugServer

    with TRACER.span(tracing.CRYPTO_PACK, lanes=1):
        pass

    loop = asyncio.new_event_loop()
    srv = DebugServer()

    def run():
        asyncio.set_event_loop(loop)
        loop.run_forever()

    th = threading.Thread(target=run, daemon=True)
    th.start()
    try:
        fut = asyncio.run_coroutine_threadsafe(srv.start(), loop)
        port = fut.result(10)
        out = tmp_path / "trace.json"
        rc = main(["debug", "trace", str(out),
                   "--pprof-laddr", f"127.0.0.1:{port}"])
        assert rc == 0
        doc = json.loads(out.read_text())
        assert any(e["name"] == tracing.CRYPTO_PACK
                   for e in doc["traceEvents"])
    finally:
        loop.call_soon_threadsafe(srv.close)
        loop.call_soon_threadsafe(loop.stop)
        th.join(timeout=10)


# ------------------------------------------------ round-5 regression fixes


def test_wal_repair_survives_concurrent_append(tmp_path, monkeypatch):
    """_decode_file must report the size of the bytes it actually
    read: a record appended between the read and a re-stat used to
    make repair() truncate the valid new record off a healthy WAL."""
    from tendermint_tpu.consensus import wal as walmod
    from tendermint_tpu.consensus.wal import WAL, EndHeightMessage

    path = str(tmp_path / "wal")
    w = WAL(path)
    w.write_sync(EndHeightMessage(1))
    w.write_sync(EndHeightMessage(2))
    w.close()

    w2 = WAL(path)
    orig_read = WAL._read_bytes
    state = {"raced": False}

    def racing_read(p):
        # simulate an append landing right after the repair scan's read
        data = orig_read(p)
        if p == path and not state["raced"]:
            state["raced"] = True
            body = walmod._encode_wal_msg(
                walmod.TimedWALMessage(0, EndHeightMessage(3)))
            with open(p, "ab") as f:
                f.write(walmod._FRAME.pack(zlib.crc32(body), len(body))
                        + body)
        return data

    monkeypatch.setattr(WAL, "_read_bytes", staticmethod(racing_read))
    assert w2.repair() is False
    w2.close()
    monkeypatch.undo()
    heights = [m.msg.height for m in WAL.decode_all(path)]
    assert heights == [1, 2, 3], "repair() truncated a valid record"


def test_wal_repair_still_cuts_torn_tail(tmp_path):
    from tendermint_tpu.consensus.wal import WAL, EndHeightMessage

    path = str(tmp_path / "wal")
    w = WAL(path)
    w.write_sync(EndHeightMessage(1))
    w.close()
    with open(path, "ab") as f:
        f.write(b"\xde\xad\xbe\xef" * 5)  # torn frame
    w2 = WAL(path)
    assert w2.repair() is True
    w2.close()
    assert [m.msg.height for m in WAL.decode_all(path)] == [1]


def test_blockid_iszero_gates_canonicalization():
    """Nil canonicalization follows reference IsZero (empty hash AND
    zero part_set_header), not is_nil()'s hash-only check — an
    empty-hash BlockID with a real part-set header must still encode
    or sign bytes diverge from the reference."""
    from tendermint_tpu.encoding.proto import encode_varint
    from tendermint_tpu.types import canonical
    from tendermint_tpu.types.block import (
        BlockID, PartSetHeader, block_id_writer, zero_block_id_bytes,
    )

    psh = PartSetHeader(4, b"\xaa" * 32)
    empty_hash = BlockID(b"", psh)
    assert empty_hash.is_nil() and not empty_hash.is_zero()
    assert canonical.canonical_block_id_writer(empty_hash) is not None
    assert block_id_writer(empty_hash) is not None

    zero = BlockID(b"", PartSetHeader(0, b""))
    nil = BlockID(b"", None)
    for b in (zero, nil, None):
        assert b is None or b.is_zero()
        assert canonical.canonical_block_id_writer(b) is None
    # the PLAIN-proto writer keeps gogo nullable=false parity: an
    # explicit zero part_set_header (what decoding reference nil-vote
    # bytes produces) still emits byte-identically; only the None-psh
    # nil sentinel omits
    assert block_id_writer(nil) is None and block_id_writer(None) is None
    assert block_id_writer(zero).finish() == zero_block_id_bytes()

    sb = canonical.vote_sign_bytes("c", 2, 5, 0, empty_hash, 123)
    sb_nil = canonical.vote_sign_bytes("c", 2, 5, 0, None, 123)
    assert sb != sb_nil
    assert canonical.vote_sign_bytes("c", 2, 5, 0, zero, 123) == sb_nil
    # the template-split invariant (device sign-byte assembly) still
    # holds for the newly-encoding case
    pre, suf = canonical.vote_sign_parts("c", 2, 5, 0, empty_hash)
    tsf = canonical.ts_field_bytes(123)
    assert sb == encode_varint(len(pre) + len(tsf) + len(suf)) \
        + pre + tsf + suf


def test_pex_strikes_decay_but_survive_accepts(monkeypatch):
    """Timestamped flood strikes: (a) strikes older than one bar
    expire, so an innocent config-skewed peer is never flagged no
    matter how long it runs; (b) strikes are NOT reset by an accepted
    request, so a peer sustaining over-rate requests inside one bar is
    flagged even when it sneaks a legitimate request in between (the
    old counter reset on accept and was never reachable at sustained
    ~2.5x pacing)."""
    # the p2p package imports the secret-connection stack at module
    # load; skip where its dependency is absent (test_p2p.py already
    # fails collection outright there)
    pytest.importorskip("cryptography")
    from tendermint_tpu.p2p.pex import reactor as pexmod
    from tendermint_tpu.p2p.pex.addrbook import AddrBook
    from tendermint_tpu.p2p.pex.reactor import PEX_CHANNEL, PEXReactor

    clock = {"now": 1000.0}

    class _T:
        @staticmethod
        def monotonic():
            return clock["now"]

    monkeypatch.setattr(pexmod, "time", _T)

    class FakePeer:
        def __init__(self, pid):
            self.id = pid
            self.outbound = False
            self.socket_addr = ""
            self.sent = []

        async def send(self, chan, msg):
            self.sent.append(msg)

    req = json.dumps({"type": "pex_request"}).encode()

    async def recv_at(rx, peer, t):
        clock["now"] = t
        await rx.receive(PEX_CHANNEL, peer, req)

    async def go():
        # ensure_period 0.5 -> receiver bar (request_interval) = 1.0
        rx = PEXReactor(AddrBook(), ensure_period=0.5)
        assert rx.request_interval == 1.0

        # (b) sustained over-rate with an accept snuck in: flagged
        flooder = FakePeer("ab" * 20)
        await recv_at(rx, flooder, 1000.0)    # accepted
        await recv_at(rx, flooder, 1000.30)   # strike 1
        await recv_at(rx, flooder, 1001.05)   # accepted (>= bar)
        await recv_at(rx, flooder, 1001.15)   # strike 2 (1 survives accept)
        with pytest.raises(ValueError, match="flood"):
            await recv_at(rx, flooder, 1001.25)  # strike 3 inside one bar
        assert len(flooder.sent) == 2

        # (a) mild skew forever: one early request per bar, strikes
        # expire before they can ever accumulate to the threshold
        skewed = FakePeer("cd" * 20)
        t = 2000.0
        await recv_at(rx, skewed, t)          # accepted
        for _ in range(10):
            await recv_at(rx, skewed, t + 0.5)   # early: strike
            t += 1.5
            await recv_at(rx, skewed, t)         # accepted
        assert len(skewed.sent) == 11
        assert len(rx._flood_strikes.get(skewed.id, [])) <= 2

    asyncio.run(go())


def test_leaf_fold_ns_and_lookback_widen_a_keyed_run():
    """A keyed run whose units are whole requests (the light proxy's):
    `fold_ns` is the pause AND the overlap a run survives, `lookback`
    how far back its entry is looked for."""
    import time

    t = tracing.Tracer(capacity=1024)
    now = time.perf_counter_ns()
    kind = tracing.LIGHT_REQUEST

    def unit(start, **kw):
        t.leaf(kind, start, fold_key=kind, **kw)

    # a unit that began a second before the run's end: the default
    # refuses the overlap, LIGHT_FOLD_NS takes it
    unit(now - 1_000)
    unit(now - 1_000_000_000)
    assert len(t) == 2
    t.clear()
    wide = dict(fold_ns=tracing.LIGHT_FOLD_NS,
                lookback=tracing.LIGHT_FOLD_LOOKBACK)
    unit(now - 1_000, **wide)
    unit(now - 1_000_000_000, hits=1, **wide)
    for _ in range(tracing.LEAF_KEY_LOOKBACK + 8):   # a few launches
        with t.span(tracing.CRYPTO_PACK):
            pass
    unit(time.perf_counter_ns() - 500, hits=1, **wide)
    (entry,) = [r for r in t.snapshot() if r[0] == kind]
    assert entry[6]["n"] == 3 and entry[6]["hits"] == 2
    assert entry[5] >= 1_000_000_000     # the extent its units spread over
    # past the wider lookback the run ends all the same
    for _ in range(tracing.LIGHT_FOLD_LOOKBACK):
        with t.span(tracing.CRYPTO_PACK):
            pass
    unit(time.perf_counter_ns() - 500, **wide)
    assert len([r for r in t.snapshot() if r[0] == kind]) == 2


def test_light_leaf_folds_by_kind():
    import time

    tracing.TRACER.clear()
    for kind in (tracing.LIGHT_FETCH, tracing.LIGHT_PLAN) * 3:
        tracing.light_leaf(kind, time.perf_counter_ns() - 100, lanes=1)
    got = {r[0]: r[6] for r in tracing.TRACER.snapshot()}
    assert got[tracing.LIGHT_FETCH]["n"] == 3
    assert got[tracing.LIGHT_PLAN]["lanes"] == 3
    tracing.TRACER.clear()



def test_quiet_records_nothing_beneath_it():
    """Inside Tracer.quiet() no span and no leaf reaches the ring, on
    this task alone, and what was current comes back after it."""
    import time

    t = tracing.Tracer(capacity=64)
    with t.span(tracing.CONSENSUS_HEIGHT) as outer:
        with t.quiet():
            with t.span(tracing.VERIFY_COLLECT) as inner:
                inner.set_attr("lanes", 3)
                with t.span(tracing.VERIFY_SIGN_BATCH):
                    pass
            t.leaf(tracing.DB_WRITE, time.perf_counter_ns() - 10)
            assert t.begin(tracing.CRYPTO_PACK) is tracing.NOOP_SPAN
            (t.current() or tracing.NOOP_SPAN).set_attr("backend", "x")
        assert t.current() is outer
        with t.span(tracing.VERIFY_COLLECT):
            pass
    assert [r[0] for r in t.snapshot()] == [
        tracing.VERIFY_COLLECT, tracing.CONSENSUS_HEIGHT]
