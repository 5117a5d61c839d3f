"""Multi-chip verify fabric (forced 8-device host mesh — conftest.py
sets --xla_force_host_platform_device_count=8, so EVERY tier-1 run
exercises the mesh paths):

  * key-range-sharded expanded comb tables — verdict parity with the
    replicated single-chip path, including a key set straddling shard
    boundaries (partial + empty shards), and the lifted valset cap
    (a build beyond the single-chip budget succeeds sharded where the
    replicated path raises);
  * padded mesh dispatch — an odd bucket (e.g. 10,001 lanes) pads up
    to a device multiple and keeps the mesh instead of silently
    dropping to one device (pinned with a recording fake kernel so
    the tier-1 envelope doesn't pay a 16k-lane compile);
  * per-device ResidentArena shards — round-robin slot routing,
    per-DEVICE delta accounting at ~1/8 of the single-arena upload,
    and per-shard known-answer sentinels attributing a wrong-verdict
    chip individually (breaker opens, host re-verifies, the failing
    device is named);
  * the three fabric metrics (tpu_mesh_devices, tpu_shard_lanes_total,
    tpu_table_shard_bytes) registered and moving;
  * mesh self-healing — per-device breakers evicting a single chip
    (live reshard to 7 shards, verdict parity full -> degraded ->
    re-admitted), dispatch continuity across an eviction between
    launches, the `device.shard_fail` failpoint, and the arena's
    ensure_mesh() re-splice.

The 10,240-lane commit acceptance (sharded tables + mesh arena +
speculation serve at full size), its degraded twin (device.shard_fail
armed on one chip, 7-survivor verdicts + half-open re-admission) and
the real sr25519 mesh parity run in the slow tier — they are
real-kernel compiles the tier-1 envelope cannot afford cold.
"""

import hashlib

import numpy as np
import pytest

from tendermint_tpu.crypto import batch as cbatch
from tendermint_tpu.crypto import ed25519_ref as ref
from tendermint_tpu.crypto.tpu import expanded as ex
from tendermint_tpu.crypto.tpu import ledger as ld
from tendermint_tpu.crypto.tpu import resident as rs
from tendermint_tpu.crypto.tpu import verify as tv
from tendermint_tpu.libs import failpoints
from tendermint_tpu.libs.metrics import tpu_metrics


@pytest.fixture(autouse=True)
def _restore_fabric_knobs():
    yield
    ex.set_shard_crossover(None)
    rs.set_arena_shards(True)
    failpoints.disarm("device.shard_fail")
    cbatch.reset_breakers()


def _mesh8():
    mesh = tv._mesh()
    assert mesh is not None and mesh.devices.size == 8, \
        "tests need the conftest-forced 8-device host mesh"
    return mesh


def _submesh(n):
    """A mesh over the first n host devices (to exercise bucket sizes
    the full mesh divides evenly)."""
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:n]), ("dp",))


def _keys(n, tag=b"mc"):
    seeds = [hashlib.sha256(tag + b"%d" % i).digest() for i in range(n)]
    return seeds, [ref.public_key_from_seed(s) for s in seeds]


def _lanes(seeds, n_lanes, tamper=()):
    """(idx, msgs, sigs, expect): lanes cycling over every key —
    straddling every shard boundary — with per-lane corruptions."""
    n_keys = len(seeds)
    idx, msgs, sigs, expect = [], [], [], []
    for i in range(n_lanes):
        vi = i % n_keys
        msg = b"multichip lane %d" % i
        sig = ref.sign(seeds[vi], msg)
        ok = True
        if i in tamper:
            kind = tamper[i]
            if kind == "bad-sig":
                sig = sig[:32] + bytes(32)
            elif kind == "wrong-lane":
                sig = ref.sign(seeds[(vi + 1) % n_keys], msg)
            elif kind == "malformed":
                sig = b"\x07" * 63
            ok = False
        idx.append(vi)
        msgs.append(msg)
        sigs.append(sig)
        expect.append(ok)
    return idx, msgs, sigs, expect


# ---------------------------------------------------- mesh + metrics


def test_mesh_present_and_gauge():
    _mesh8()
    assert tpu_metrics().mesh_devices.value() == 8


def test_fabric_metrics_registered():
    # the three fabric metrics exist under the tpu namespace with the
    # documented names (check_metrics pins docs-table sync suite-wide)
    m = tpu_metrics()
    assert m.mesh_devices.name == "tpu_mesh_devices"
    assert m.shard_lanes.name == "tpu_shard_lanes_total"
    assert m.table_shard_bytes.name == "tpu_table_shard_bytes"


def test_mesh_lane_pad_math():
    mesh = _mesh8()
    assert tv.mesh_lane_pad(2048, mesh) == 2048
    assert tv.mesh_lane_pad(16384, mesh) == 16384
    m3 = _submesh(3)
    assert tv.mesh_lane_pad(256, m3) == 258
    assert tv.mesh_lane_pad(16384, m3) == 16386


# -------------------------------- padded dispatch (no kernel compile)


def test_odd_bucket_takes_mesh_via_padding(monkeypatch):
    """A 10,001-lane batch on a mesh that doesn't divide its bucket
    (3 devices vs the 16,384 bucket) must PAD to the next device
    multiple and stay sharded — not fall back to a single device.
    Pinned with a recording fake kernel: the tier-1 envelope cannot
    afford the real 16k-lane compile."""
    mesh = _submesh(3)
    monkeypatch.setattr(tv, "_mesh", lambda: mesh)
    seen = {}

    def fake_kernel():
        def k(*, btab, ab, sb, msg, nblocks, s_ok):
            seen["bucket"] = ab.shape[0]
            seen["sharded"] = hasattr(ab, "sharding") and \
                getattr(ab.sharding, "mesh", None) is not None
            return np.ones(ab.shape[0], bool)
        return k

    monkeypatch.setattr(tv, "_kernel", fake_kernel)
    n = 10_001
    seed = hashlib.sha256(b"odd").digest()
    pub = ref.public_key_from_seed(seed)
    msg = b"m"
    sig = ref.sign(seed, msg)
    before = tpu_metrics().shard_lanes.value(device="2")
    out = tv.verify_batch([pub] * n, [msg] * n, [sig] * n)
    assert len(out) == n and bool(out.all())
    # _chunks(10_001) -> one 16,384 bucket; 16384 % 3 != 0 -> 16386
    assert seen["bucket"] == 16386
    assert seen["sharded"], "odd bucket fell off the mesh"
    assert tpu_metrics().shard_lanes.value(device="2") - before == 5462


def test_expanded_shard_args_pads_odd_bucket(monkeypatch):
    """The expanded replicated path's lane sharding pads odd buckets
    too (the pre-fabric code silently went single-device)."""
    mesh = _submesh(3)
    monkeypatch.setattr(tv, "_mesh", lambda: mesh)
    monkeypatch.setattr(tv, "_SHARD_MIN", 128)
    dummy = type("E", (), {})()
    dummy.sharded = False
    dummy.mesh = mesh  # _shard_args lanes follow the placement mesh
    idx = np.zeros(256, np.int32)
    fields = {"sb": np.zeros((256, 64), np.uint8),
              "s_ok": np.zeros(256, bool),
              "pre": np.zeros((4, 16), np.uint8)}
    oidx, ofields, _btab = ex.ExpandedKeys._shard_args(
        dummy, idx, fields, repl_keys=("pre",))
    assert oidx.shape[0] == 258
    assert ofields["sb"].shape[0] == 258
    assert ofields["pre"].shape == (4, 16)  # replicated: not padded
    assert getattr(oidx, "sharding", None) is not None


# ------------------------- key-range-sharded tables (real kernels)


@pytest.fixture(scope="module")
def sharded_keys():
    """ONE sharded build shared by the sharded-table tests: 30 keys
    over 8 devices -> 4 keys/shard with shard 7 holding only 2 real
    keys (28, 29) + 2 padding keys — the straddle case. The build
    succeeds BEYOND the forced single-chip crossover (8 keys), i.e.
    where a replicated single-chip build is out of budget."""
    seeds, pubs = _keys(30)
    ex.set_shard_crossover(8)
    try:
        shd = ex.ExpandedKeys(pubs)
    finally:
        ex.set_shard_crossover(None)
    return seeds, pubs, shd


def test_sharded_tables_verdict_parity(sharded_keys):
    """48 lanes cycling every key (so every shard boundary is
    straddled), corrupt lanes included, agree lane-for-lane with the
    reference oracle — which the replicated single-device path is
    pinned against throughout test_tpu_verify/test_structured_verify,
    so single-vs-mesh parity is anchored on both sides. (The explicit
    10,240-lane single-vs-mesh device A/B runs in the slow tier.)"""
    seeds, _pubs, shd = sharded_keys
    assert shd.sharded and shd.n_shards == 8 and \
        shd.keys_per_shard == 4
    tamper = {5: "bad-sig", 11: "wrong-lane", 17: "malformed"}
    idx, msgs, sigs, expect = _lanes(seeds, 48, tamper)
    before = tpu_metrics().shard_lanes.value(device="0")
    got = np.asarray(shd.verify(idx, msgs, sigs))
    assert list(got) == expect, "sharded verdicts diverged from oracle"
    # per-chip HBM is 1/8 of the (padded-to-32-keys) table
    assert tpu_metrics().table_shard_bytes.value() == \
        int(shd.tables.nbytes) // 8
    # routing counted real lanes onto device 0 (keys 0-3 -> shard 0)
    assert tpu_metrics().shard_lanes.value(device="0") > before


def test_sharded_tables_boundary_and_empty_shards(sharded_keys):
    """Lanes pinned to the exact shard-boundary keys (3|4, 27|28) and
    the partial last shard verify correctly; a batch touching only
    shard 0's keys leaves shards 1-7 with pure padding lanes (the
    empty-shard launch) and still verifies."""
    seeds, _pubs, shd = sharded_keys
    for bidx in ([3, 4, 27, 28, 29, 0], [0, 1, 2, 3, 0, 1]):
        bmsgs = [b"boundary lane %d" % i for i in range(len(bidx))]
        bsigs = [ref.sign(seeds[k], m) for k, m in zip(bidx, bmsgs)]
        got = shd.verify(bidx, bmsgs, bsigs)
        assert bool(np.asarray(got).all()), bidx


def test_build_beyond_single_chip_budget(monkeypatch, sharded_keys):
    """The lifted cap: with the single-chip budget below the valset, a
    replicated build RAISES without a mesh (the pre-fabric failure),
    while the fixture's sharded build of the same size succeeded on
    the mesh — and max_keys() stays the CPU build-chunk cap for the
    _use_expanded policy (virtual CPU shards share one RAM)."""
    _seeds, pubs, shd = sharded_keys
    assert shd.sharded and len(shd) == 30  # the succeeds-on-mesh leg
    monkeypatch.setattr(ex, "_single_chip_max_keys", lambda: 16)
    monkeypatch.setattr(tv, "_mesh", lambda: None)
    with pytest.raises(ValueError, match="single-chip table budget"):
        ex.ExpandedKeys(pubs)
    assert ex.max_keys() == 16  # delegates to the single-chip budget
    monkeypatch.undo()
    # a crossover misconfigured ABOVE the budget degrades to sharding
    # on a mesh (never a per-commit ValueError churning the breaker)
    monkeypatch.setattr(ex, "_single_chip_max_keys", lambda: 16)
    ex.set_shard_crossover(10 ** 6)
    try:
        assert ex.ExpandedKeys(pubs).sharded
    finally:
        ex.set_shard_crossover(None)
    monkeypatch.undo()
    # the _use_expanded policy cap on the CPU backend ignores the
    # virtual mesh entirely: shards share one host RAM, so big builds
    # buy nothing there (max_keys lifts N-fold only on real chips)
    assert ex.max_keys() == ex.ExpandedKeys.BUILD_CHUNK


def test_general_kernel_mesh_parity(monkeypatch):
    """Verdict parity single-vs-mesh for the GENERAL kernel: the same
    120-lane batch (bucket 128, short messages — the shape the suite
    already compiles single-device) through the 8-device lane-sharded
    launch and the forced single-device launch, corrupt lanes
    included."""
    seeds, pubs = _keys(24, tag=b"gp")
    idx, msgs, sigs, expect = _lanes(
        seeds, 120, {5: "bad-sig", 40: "malformed"})
    gp = [pubs[i] for i in idx]
    monkeypatch.setattr(tv, "_SHARD_MIN", 128)
    got_mesh = tv.verify_batch(gp, msgs, sigs)
    monkeypatch.setattr(tv, "_mesh", lambda: None)
    got_single = tv.verify_batch(gp, msgs, sigs)
    assert (np.asarray(got_mesh) == np.asarray(got_single)).all()
    assert list(got_mesh) == expect


def test_shard_crossover_knob_roundtrip():
    ex.set_shard_crossover(512)
    assert ex.shard_crossover_keys() == 512
    ex.set_shard_crossover(None)
    assert ex.shard_crossover_keys() == ex._single_chip_max_keys()


# ---------------------------- per-device arena shards (no launches)


def _splice_args(arena, n):
    from tendermint_tpu.types import sign_batch as sbm

    arena.set_template(1, b"\x01" * 10, b"\x02" * 4)
    ts = np.asarray([10 ** 18 + i for i in range(n)], np.int64)
    group = np.ones(n, np.int32)
    patch, split, patch_len = sbm._build_patches(
        arena.pre_len.astype(np.int64), arena.suf_len, group, ts)
    # per-lane-unique rows (7 coprime with 256), so a routing mixup
    # can never alias two lanes' bytes
    sig_rows = (np.arange(n)[:, None] * 7
                + np.arange(64)[None, :]).astype(np.uint8)
    return sig_rows, patch, split, patch_len, group


def test_mesh_arena_routing_and_delta_accounting():
    """Round-robin slot routing lands app lane i on shard i % 8, and a
    full-commit splice uploads ~1/8 of the single-arena bytes PER
    DEVICE — the acceptance bound (single bytes / 8 + per-shard
    template overhead)."""
    mesh = _mesh8()
    arena = rs.MeshResidentArena(65, mesh=mesh)
    assert arena.n_shards == 8
    assert arena.capacity == 1 + 8 * (arena.shard_capacity - 1)
    _seeds, pubs = _keys(64, tag=b"ar")
    arena.install_keys(pubs)
    args = _splice_args(arena, 64)
    single = rs.ResidentArena(65)
    sargs = _splice_args(single, 64)
    slots = list(range(1, 65))
    # donation reuse pinned across the steady-state splice: grab the
    # shard-2 buffer pointer BEFORE any host read of _sb (a CPU-
    # backend view would pin the buffer and defeat aliasing)
    p0 = arena.buffer_pointer("sb", shard=2)
    arena.splice(slots, *args)
    p1 = arena.buffer_pointer("sb", shard=2)
    if p0 is not None and p1 is not None:
        assert p0 == p1, "sharded donated splice re-allocated"
    single.splice(slots, *sargs)
    # routing: app lane 0 -> shard 0 slot 1; lane 11 -> shard 3 slot 2
    sb = np.array(arena._sb)  # (D, per, 64)
    assert (sb[0, 1] == args[0][0]).all()
    assert (sb[3, 2] == args[0][11]).all()
    assert bytes(np.array(arena._ab)[3, 2]) == pubs[11]
    per = arena.shard_reupload_bytes()
    assert max(per) <= single.reupload_bytes // 8 + 64, \
        (per, single.reupload_bytes)
    assert arena.reupload_bytes == sum(per)
    # sentinel rows untouched by the full splice
    assert (sb[:, 0] == sb[0, 0]).all()
    # deactivate keeps every shard's sentinel
    arena.deactivate_all()
    act = np.array(arena._active)
    assert act[:, 0].all() and not act[:, 1:].any()


def _fake_mesh_kernel(bad_shard):
    """A stand-in _mesh_arena_kernel whose device `bad_shard` returns
    wrong verdicts (its sentinel dies with the rest)."""
    def build(width):
        def k(ab, sb, s_ok, active, pre, pre_len, suf, suf_len,
              patch, split, patch_len, group, btab):
            out = np.asarray(active).copy()
            out[bad_shard] = False
            return out
        return k
    return build


def test_mesh_arena_launch_order_and_sentinels(monkeypatch):
    """launch() returns GLOBAL-slot-ordered verdicts and per-shard
    sentinel results (faked kernel: shard 2's device lies)."""
    monkeypatch.setattr(rs, "_mesh_arena_kernel", _fake_mesh_kernel(2))
    arena = rs.MeshResidentArena(65, mesh=_mesh8())
    args = _splice_args(arena, 64)
    arena.splice(list(range(1, 65)), *args)
    verd = arena.launch()
    assert arena.sentinel_ok == [True] * 2 + [False] + [True] * 5
    assert not verd[0], "aggregate sentinel must fail when any shard does"
    assert arena.failed_shards()[0][0] == 2
    # shard 2 owns app lanes 2, 10, 18, ... -> global slots 3, 11, ...
    assert not verd[3] and not verd[11]
    assert verd[1] and verd[2] and verd[4]


def test_speculation_attributes_failing_shard(monkeypatch, caplog):
    """Per-shard sentinel -> breaker attribution through the REAL
    speculation plane: one lying chip opens ITS OWN per-device breaker
    (backend breaker stays closed — the other 7 devices keep serving)
    with the shard/device named, every lane re-verifies on host, and
    the commit still serves correct verdicts."""
    import logging

    from helpers import CHAIN_ID, make_genesis_state_and_pvs
    from tendermint_tpu.config import SpeculationConfig
    from tendermint_tpu.consensus.speculation import SpeculationPlane
    from tendermint_tpu.libs.metrics import speculation_metrics
    from tendermint_tpu.types.block import (
        BlockID, BlockIDFlag, Commit, CommitSig, PartSetHeader,
    )
    from tendermint_tpu.types.vote import Vote, VoteType

    monkeypatch.setattr(rs, "_mesh_arena_kernel", _fake_mesh_kernel(1))
    state, pvs = make_genesis_state_and_pvs(4)
    vals = state.validators
    chain_id = CHAIN_ID
    bid = BlockID(b"\xab" * 32, PartSetHeader(1, b"\xcd" * 32))
    h = 5
    plane = SpeculationPlane(SpeculationConfig(arena_lanes=16),
                             device_min=1)
    plane.begin_height(chain_id, vals, h, 0, bid)
    by_addr = {pv.get_pub_key().address(): pv for pv in pvs}
    cs = []
    for idx, val in enumerate(vals.validators):
        v = Vote(type=VoteType.PRECOMMIT, height=h, round=0,
                 block_id=bid,
                 timestamp=1_700_000_000_000_000_000 + idx,
                 validator_address=val.address, validator_index=idx)
        by_addr[val.address].sign_vote(chain_id, v)
        plane.observe_precommit(v)
        cs.append(CommitSig(BlockIDFlag.COMMIT, val.address,
                            v.timestamp, v.signature))
    host_before = speculation_metrics().launches.value(
        backend="host_recheck")
    with caplog.at_level(logging.ERROR):
        plane.flush_sync()
    assert isinstance(plane._arena, rs.MeshResidentArena)
    failed = plane._arena.failed_shards()
    assert failed, "a shard sentinel must fail"
    # attribution is PER DEVICE: only the lying chip's breaker opens;
    # the backend breaker stays closed so the fabric keeps serving on
    # the 7 survivors (pre-self-healing this evicted the whole backend)
    assert cbatch.breaker("ed25519").state == cbatch.CLOSED
    states = cbatch.device_breaker_states("ed25519")
    assert states.get(failed[0][1]) == cbatch.OPEN
    assert sum(1 for s in states.values() if s != cbatch.CLOSED) == 1
    assert any("shard 1" in r.message for r in caplog.records), \
        "the failing shard/device must be named in the log"
    assert speculation_metrics().launches.value(
        backend="host_recheck") - host_before == 1
    # host re-verify stored CORRECT verdicts: the commit serves
    commit = Commit(h, 0, bid, cs)
    cbatch.reset_breakers()
    assert plane.serve_commit(vals, chain_id, bid, h, commit)
    plane.close()


def test_make_arena_respects_knob():
    assert isinstance(rs.make_arena(8), rs.MeshResidentArena)
    rs.set_arena_shards(False)
    assert isinstance(rs.make_arena(8), rs.ResidentArena)


def test_sr25519_padded_dispatch_shape(monkeypatch):
    """sr25519 takes the same padded lane-shard dispatch: an odd
    bucket on a 3-device mesh pads to a device multiple and stays
    sharded (recording fake kernel; real-verdict mesh parity runs in
    the slow tier)."""
    from tendermint_tpu.crypto import sr25519_ref as srr
    from tendermint_tpu.crypto.tpu import sr_verify

    mesh = _submesh(3)
    monkeypatch.setattr(tv, "_mesh", lambda: mesh)
    monkeypatch.setattr(tv, "_SHARD_MIN", 128)
    seen = {}

    def fake_kernel():
        def k(*, btab, s0, ab, rb, blocks, counts, sb, a_pre, r_pre,
              s_ok):
            seen["bucket"] = ab.shape[0]
            seen["sharded"] = all(
                getattr(getattr(v, "sharding", None), "mesh", None)
                is not None for v in (ab, blocks, counts, sb))
            return np.ones(ab.shape[0], bool)
        return k

    monkeypatch.setattr(sr_verify, "_kernel", fake_kernel)
    mini = hashlib.sha256(b"sr").digest()
    pub = srr.public_key_from_mini(mini)
    msg = b"m"
    sig = srr.sign(mini, msg)
    n = 100  # bucket 128 -> 129 on a 3-device mesh
    out = sr_verify.verify_batch_sr([pub] * n, [msg] * n, [sig] * n)
    assert len(out) == n and bool(out.all())
    assert seen["bucket"] == 129
    assert seen["sharded"], "sr bucket fell off the mesh"


# ----------------------- mesh self-healing (per-device breakers)


def test_live_reshard_parity_evict_and_readmit(sharded_keys):
    """The self-healing lifecycle on real kernels: full-mesh verdicts,
    degraded (7-shard) verdicts after one device is evicted, and
    re-admitted (8-shard) verdicts are byte-identical over the 30-key
    straddle/partial fixture; the eviction is counted, the backend
    breaker never opens, and the launch ledger stamps the degraded
    launch with the 7 surviving devices."""
    seeds, _pubs, shd = sharded_keys
    mesh = _mesh8()
    victim = str(mesh.devices.flat[5])
    tamper = {5: "bad-sig", 11: "wrong-lane", 17: "malformed"}
    idx, msgs, sigs, expect = _lanes(seeds, 48, tamper)
    full = np.asarray(shd.verify(idx, msgs, sigs))
    assert list(full) == expect and shd.n_shards == 8
    ev_before = tpu_metrics().mesh_evictions.value(
        device=victim, reason="launch_error")
    cbatch.mark_device_failed("ed25519", device=victim)
    try:
        deg = np.asarray(shd.verify(idx, msgs, sigs))
        assert shd.n_shards == 7, "fabric did not reshard"
        assert victim not in [str(d) for d in shd.mesh.devices.flat]
        assert (deg == full).all(), \
            "degraded verdicts diverged from full-mesh"
        assert cbatch.breaker("ed25519").state == cbatch.CLOSED
        assert tpu_metrics().mesh_evictions.value(
            device=victim, reason="launch_error") == ev_before + 1
        stamped = [r for r in ld.snapshot() if r.get("active_devices")]
        assert stamped and len(stamped[-1]["active_devices"]) == 7
        assert victim not in stamped[-1]["active_devices"]
    finally:
        cbatch.readmit_device("ed25519", victim)
    back = np.asarray(shd.verify(idx, msgs, sigs))
    assert shd.n_shards == 8 and shd.keys_per_shard == 4
    assert (back == full).all(), "re-admitted verdicts diverged"


def test_continuity_eviction_between_launches(monkeypatch):
    """10,001 lanes through the general kernel with a device evicted
    BETWEEN launches: the next dispatch pads to the 7-device multiple
    and rides the surviving mesh — no single-device collapse, no
    backend-wide fallback (recording fake kernel: tier-1 cannot afford
    the 16k-lane compile)."""
    mesh = _mesh8()
    seen = {}

    def fake_kernel():
        def k(*, btab, ab, sb, msg, nblocks, s_ok):
            seen["bucket"] = ab.shape[0]
            m = getattr(getattr(ab, "sharding", None), "mesh", None)
            seen["devices"] = int(m.devices.size) if m is not None \
                else 1
            return np.ones(ab.shape[0], bool)
        return k

    monkeypatch.setattr(tv, "_kernel", fake_kernel)
    n = 10_001
    seed = hashlib.sha256(b"cont").digest()
    pub = ref.public_key_from_seed(seed)
    msg = b"m"
    sig = ref.sign(seed, msg)
    out = tv.verify_batch([pub] * n, [msg] * n, [sig] * n)
    assert len(out) == n and bool(out.all())
    assert seen["devices"] == 8 and seen["bucket"] == 16384
    cbatch.mark_device_failed(
        "ed25519", device=str(mesh.devices.flat[3]))
    out = tv.verify_batch([pub] * n, [msg] * n, [sig] * n)
    assert len(out) == n and bool(out.all())
    # 16,384 % 7 != 0 -> padded to the next 7-multiple on survivors
    assert seen["devices"] == 7 and seen["bucket"] == 16387
    assert cbatch.breaker("ed25519").state == cbatch.CLOSED


def test_device_shard_fail_failpoint_evicts_one_chip(monkeypatch):
    """`device.shard_fail` armed corrupt;nth=3 mangles the 3rd mesh
    device's payload at dispatch entry: exactly that chip is evicted
    (reason=failpoint), the same dispatch already rides the 7
    survivors, and the backend breaker never opens."""
    mesh = _mesh8()
    victim = str(mesh.devices.flat[2])
    monkeypatch.setattr(tv, "_SHARD_MIN", 128)
    seen = {}

    def fake_kernel():
        def k(*, btab, ab, sb, msg, nblocks, s_ok):
            m = getattr(getattr(ab, "sharding", None), "mesh", None)
            seen["devices"] = int(m.devices.size) if m is not None \
                else 1
            return np.ones(ab.shape[0], bool)
        return k

    monkeypatch.setattr(tv, "_kernel", fake_kernel)
    seed = hashlib.sha256(b"fp").digest()
    pub = ref.public_key_from_seed(seed)
    msg = b"m"
    sig = ref.sign(seed, msg)
    fp_before = tpu_metrics().mesh_evictions.value(
        device=victim, reason="failpoint")
    failpoints.arm("device.shard_fail", "corrupt", nth=3)
    try:
        out = tv.verify_batch([pub] * 120, [msg] * 120, [sig] * 120)
    finally:
        failpoints.disarm("device.shard_fail")
    assert len(out) == 120 and bool(out.all())
    assert cbatch.evicted_devices("ed25519") == [victim]
    assert cbatch.device_breaker_states("ed25519")[victim] == \
        cbatch.OPEN
    assert cbatch.breaker("ed25519").state == cbatch.CLOSED
    assert seen["devices"] == 7, "dispatch did not exclude the chip"
    assert tpu_metrics().mesh_evictions.value(
        device=victim, reason="failpoint") == fp_before + 1


def test_mesh_arena_reshards_after_eviction():
    """MeshResidentArena.ensure_mesh() re-splices the global slot
    round-robin over the surviving shards: installed keys land on
    their new home devices and the arena reports the degraded width
    (no launches — placement + routing only)."""
    mesh = _mesh8()
    arena = rs.MeshResidentArena(65, mesh=mesh)
    _seeds, pubs = _keys(64, tag=b"rm")
    arena.install_keys(pubs)
    assert arena.n_shards == 8
    cbatch.mark_device_failed(
        "ed25519", device=str(mesh.devices.flat[6]))
    assert arena.ensure_mesh() is True
    assert arena.n_shards == 7
    # key slots replayed onto the 7-wide round-robin: app lane 8
    # (global slot 9) now lives on shard (9-1) % 7 + ... -> spot-check
    # via the device-resident key bytes
    found = 0
    ab = np.array(arena._ab)  # (D, per, 32)
    for d in range(arena.n_shards):
        for s in range(arena.shard_capacity):
            row = bytes(ab[d, s])
            if row in set(pubs):
                found += 1
    assert found == 64, "installed keys lost in the reshard"
    assert arena.ensure_mesh() is False  # stable: no second rebuild


# ------------------------------------------------------- slow tier


@pytest.mark.slow
def test_sr25519_mesh_parity_real_kernel(monkeypatch):
    """Real-verdict sr25519 parity: the 8-device meshed launch agrees
    lane-for-lane with the CPU-pinned single-device kernel, including
    corrupt lanes, at a bucket the old gate would have sharded only
    by luck."""
    from tendermint_tpu.crypto import sr25519_ref as srr
    from tendermint_tpu.crypto.tpu import sr_verify

    monkeypatch.setattr(tv, "_SHARD_MIN", 128)
    n = 130
    minis = [hashlib.sha256(b"srp%d" % i).digest() for i in range(n)]
    pubs = [srr.public_key_from_mini(m) for m in minis]
    msgs = [b"sr lane %d" % i for i in range(n)]
    sigs = [srr.sign(m, msg) for m, msg in zip(minis, msgs)]
    sigs[7] = sigs[7][:32] + bytes(31) + b"\x80"
    want = sr_verify.verify_batch_sr(pubs, msgs, sigs, cpu=True)
    got = sr_verify.verify_batch_sr(pubs, msgs, sigs)
    assert (np.asarray(got) == np.asarray(want)).all()
    assert not got[7] and bool(got[:7].all())


@pytest.mark.slow
def test_structured_sharded_commit_parity():
    """The production commit route over sharded tables: CommitSignBatch
    -> verify_structured routes lanes to home devices and matches the
    replicated structured path lane-for-lane."""
    import test_structured_verify as tsv
    from tendermint_tpu.types.sign_batch import CommitSignBatch

    tamper = {5: "ts", 11: "wrong-lane", 17: "malformed"}
    pubs, commit, lanes, sigs, expect = tsv._mk(tamper=tamper)
    sb = CommitSignBatch(tsv.CHAIN, commit, list(range(len(lanes))))
    ex.set_shard_crossover(8)
    try:
        shd = ex.ExpandedKeys(pubs)
    finally:
        ex.set_shard_crossover(None)
    assert shd.sharded
    got = shd.verify_structured(lanes, sb, sigs)
    assert list(got) == expect
    repl = ex.ExpandedKeys(pubs)
    assert list(repl.verify_structured(lanes, sb, sigs)) == list(got)


@pytest.mark.slow
def test_10240_lane_commit_acceptance():
    """The ISSUE acceptance at full size on the forced 8-device host
    mesh: a 10,240-lane commit verifies through key-range-sharded
    tables (valset beyond the single-chip budget) and per-device
    arena shards, verdicts byte-identical to the single-device path,
    with steady-state per-device resident re-upload <= single-device
    bytes / 8 + per-shard template overhead."""
    from tendermint_tpu.types import canonical, sign_batch as sbm
    from tendermint_tpu.types.block import BlockID, PartSetHeader
    from tendermint_tpu.types.vote import VoteType

    n, n_keys = 10_240, 320
    seeds, pubs = _keys(n_keys, tag=b"acc")
    idx = [i % n_keys for i in range(n)]
    bid = BlockID(b"\xab" * 32, PartSetHeader(4, b"\xcd" * 32))
    base_ts = 1_753_928_000_000_000_000
    msgs = [canonical.vote_sign_bytes(
        "acc-chain", int(VoteType.PRECOMMIT), 123456, 0, bid,
        base_ts + i) for i in range(n)]
    sigs = [ref.sign(seeds[idx[i]], msgs[i]) for i in range(n)]
    sigs[9_999] = sigs[9_999][:32] + bytes(32)

    # single-device reference: replicated tables, mesh disabled
    import unittest.mock as mock

    with mock.patch.object(tv, "_mesh", lambda: None):
        repl = ex.ExpandedKeys(pubs)
        want = np.asarray(repl.verify(idx, msgs, sigs))
    assert not want[9_999] and want.sum() == n - 1

    # sharded: force the crossover below the valset (stands in for a
    # >40k-key valset against the real single-chip budget)
    ex.set_shard_crossover(n_keys // 2)
    try:
        shd = ex.ExpandedKeys(pubs)
        assert shd.sharded and shd.n_shards == 8
        got = np.asarray(shd.verify(idx, msgs, sigs))
    finally:
        ex.set_shard_crossover(None)
    assert (got == want).all(), "mesh verdicts diverged at 10,240 lanes"

    # per-device arena shards at commit scale: steady-state delta
    # re-upload per DEVICE <= single-device bytes / 8 + template
    # overhead
    arena = rs.MeshResidentArena(n + 64)
    single = rs.ResidentArena(n + 64)
    pre, suf = canonical.vote_sign_parts(
        "acc-chain", int(VoteType.PRECOMMIT), 123456, 0, bid)
    for a in (arena, single):
        a.set_template(1, pre, suf)
    ts = np.asarray([base_ts + i for i in range(n)], np.int64)
    group = np.ones(n, np.int32)
    patch, split, patch_len = sbm._build_patches(
        arena.pre_len.astype(np.int64), arena.suf_len, group, ts)
    sig_rows = np.frombuffer(b"".join(sigs), np.uint8).reshape(n, 64)
    slots = list(range(1, n + 1))
    arena.splice(slots, sig_rows, patch, split, patch_len, group)
    single.splice(slots, sig_rows, patch, split, patch_len, group)
    # First fill: the power-of-two delta padding quantizes per-shard
    # buckets (1,280 rows pad to 2,048), so the per-device share is
    # ~5.5x below single-device rather than 8x — still bounded well
    # under half.
    assert max(arena.shard_reupload_bytes()) <= \
        single.reupload_bytes // 4
    # STEADY STATE (the acceptance bound): a per-flush delta of
    # arriving precommits re-uploads <= single-device bytes / 8 +
    # per-shard template overhead per device.
    d = 128
    lo_single = single.reupload_bytes
    lo_shards = arena.shard_reupload_bytes()
    single.splice(slots[:d], sig_rows[:d], patch[:d], split[:d],
                  patch_len[:d], group[:d])
    arena.splice(slots[:d], sig_rows[:d], patch[:d], split[:d],
                 patch_len[:d], group[:d])
    single_delta = single.reupload_bytes - lo_single
    per_dev = [hi - lo for hi, lo in
               zip(arena.shard_reupload_bytes(), lo_shards)]
    template_overhead = 64 + int(
        arena.pre.nbytes + arena.suf.nbytes
        + arena.pre_len.nbytes + arena.suf_len.nbytes)
    assert max(per_dev) <= single_delta // 8 + template_overhead, \
        (per_dev, single_delta)


@pytest.mark.slow
def test_10240_lane_degraded_acceptance():
    """The ISSUE self-healing acceptance at full size: with
    `device.shard_fail` armed against one device of the 8-device host
    mesh, a 10,240-lane verify over sharded tables completes with
    correct verdicts on the 7 survivors — zero backend-wide host
    fallback (backend breaker stays closed), the launch ledger stamps
    the degraded launch with 7 active devices — and the evicted chip
    re-admits through a REAL half-open known-answer probe, after
    which verdicts are byte-identical at full width again."""
    mesh = _mesh8()
    n, n_keys = 10_240, 320
    seeds, pubs = _keys(n_keys, tag=b"deg")
    idx = [i % n_keys for i in range(n)]
    msgs = [b"degraded lane %d" % i for i in range(n)]
    sigs = [ref.sign(seeds[idx[i]], msgs[i]) for i in range(n)]
    sigs[7_777] = sigs[7_777][:32] + bytes(32)
    victim = str(mesh.devices.flat[4])

    ex.set_shard_crossover(n_keys // 2)
    try:
        shd = ex.ExpandedKeys(pubs)
        assert shd.sharded and shd.n_shards == 8
        # the 5th per-device hit of the first dispatch = device index 4
        failpoints.arm("device.shard_fail", "error", nth=5)
        try:
            got = np.asarray(shd.verify(idx, msgs, sigs))
        finally:
            failpoints.disarm("device.shard_fail")
        assert cbatch.evicted_devices("ed25519") == [victim]
        assert cbatch.breaker("ed25519").state == cbatch.CLOSED, \
            "single-device failure must never open the backend breaker"
        assert shd.n_shards == 7
        assert not got[7_777] and int(got.sum()) == n - 1, \
            "degraded verdicts wrong on the survivors"
        stamped = [r for r in ld.snapshot() if r.get("active_devices")]
        assert len(stamped[-1]["active_devices"]) == 7
        assert victim not in stamped[-1]["active_devices"]
        # re-admission through the REAL half-open path: expire the
        # cooldown so the next dispatch's evicted_devices(probe=True)
        # runs the 8-lane known-answer probe pinned to the chip — it
        # passes, the breaker closes, and the same dispatch reshards
        # back to full width
        cbatch.device_breaker("ed25519", victim)._open_until = 0.0
        got2 = np.asarray(shd.verify(idx, msgs, sigs))
        assert cbatch.evicted_devices("ed25519") == []
        assert cbatch.device_breaker_states("ed25519")[victim] == \
            cbatch.CLOSED
        assert shd.n_shards == 8
        assert (got2 == got).all(), \
            "re-admitted verdicts diverged from the degraded launch"
    finally:
        ex.set_shard_crossover(None)
