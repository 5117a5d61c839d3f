"""Structured commit verify: device-assembled sign bytes must yield
verdicts identical to the bytes path (and to the host oracle).

The structured path (ExpandedKeys.verify_structured +
types/sign_batch.py) assembles each lane's canonical sign bytes ON
DEVICE from a commit-wide template and a per-lane timestamp patch.
These tests sign real canonical vote bytes, then check that the
structured kernel accepts exactly the valid lanes — across mixed
commit/nil votes, edge timestamps, a tampered timestamp, a wrong-lane
signature, and a malformed signature — matching both the bytes-path
kernel and the ed25519 reference oracle lane for lane."""

import hashlib

import numpy as np
import pytest

from tendermint_tpu.crypto import ed25519_ref as ref
from tendermint_tpu.crypto.tpu import expanded as ex
from tendermint_tpu.types.block import (
    BlockID, BlockIDFlag, Commit, CommitSig, PartSetHeader,
)
from tendermint_tpu.types.sign_batch import CommitSignBatch

CHAIN = "structured-chain"


def _mk(n_vals=24, n_lanes=48, tamper=()):
    seeds = [hashlib.sha256(b"sv%d" % i).digest() for i in range(n_vals)]
    pubs = [ref.public_key_from_seed(s) for s in seeds]
    bid = BlockID(hash=bytes(range(32)),
                  part_set_header=PartSetHeader(2, bytes(32)))
    edge_ts = [0, 1, 999_999_999, 1_000_000_000,
               1_753_928_000_123_456_789]
    sigs_objs = []
    lanes, sigs = [], []
    for i in range(n_lanes):
        flag = BlockIDFlag.NIL if i % 7 == 3 else BlockIDFlag.COMMIT
        ts = edge_ts[i % len(edge_ts)] + i
        sigs_objs.append(CommitSig(
            block_id_flag=flag,
            validator_address=bytes([i % 256] * 20),
            timestamp=ts, signature=b"",
        ))
    commit = Commit(height=977, round=1, block_id=bid,
                    signatures=sigs_objs)
    expect = []
    for i in range(n_lanes):
        vi = i % n_vals
        msg = commit.vote_sign_bytes(CHAIN, i)
        sig = ref.sign(seeds[vi], msg)
        ok = True
        if i in tamper:
            kind = tamper[i]
            if kind == "ts":
                # sign over a DIFFERENT timestamp than the commit
                # carries: the device-assembled bytes must not verify
                sigs_objs[i].timestamp += 1
                ok = False
            elif kind == "wrong-lane":
                sig = ref.sign(seeds[(vi + 1) % n_vals], msg)
                ok = False
            elif kind == "malformed":
                sig = b"\x07" * 63
                ok = False
        sigs_objs[i].signature = sig
        lanes.append(vi)
        sigs.append(sig)
        expect.append(ok)
    return pubs, commit, lanes, sigs, expect


def test_structured_matches_bytes_path_and_oracle():
    tamper = {5: "ts", 11: "wrong-lane", 17: "malformed"}
    pubs, commit, lanes, sigs, expect = _mk(tamper=tamper)
    sb = CommitSignBatch(CHAIN, commit, list(range(len(lanes))))
    e = ex.ExpandedKeys(pubs)
    got = e.verify_structured(lanes, sb, sigs)
    assert list(got) == expect
    # byte-path equivalence on the same triples
    bytes_got = e.verify(lanes, sb.materialize(), sigs)
    assert list(bytes_got) == list(got)


@pytest.mark.slow
def test_structured_all_valid_and_bucketing():
    # 130 lanes forces a padded bucket (tests pad-lane handling).
    pubs, commit, lanes, sigs, expect = _mk(n_vals=16, n_lanes=130)
    sb = CommitSignBatch(CHAIN, commit, list(range(len(lanes))))
    e = ex.ExpandedKeys(pubs)
    got = e.verify_structured(lanes, sb, sigs)
    assert all(expect) and bool(np.asarray(got).all())


def test_structured_long_chain_id():
    # Same key count (24) and lane count (48 -> bucket 64) as the
    # tamper test above: kernel shapes are keyed on (valset, bucket,
    # width), so this test compiles NO extra kernel (suite-time
    # discipline) — it reuses the cached one with different data.
    long_chain = "y" * 50
    n_vals, n = 24, 48
    seeds = [hashlib.sha256(b"sv%d" % i).digest() for i in range(n_vals)]
    pubs = [ref.public_key_from_seed(s) for s in seeds]
    bid = BlockID(hash=bytes(range(32)),
                  part_set_header=PartSetHeader(1, bytes(32)))
    sigs_objs = [CommitSig(BlockIDFlag.COMMIT, bytes([i] * 20),
                           10**18 + i, b"") for i in range(n)]
    commit = Commit(height=1 << 40, round=12, block_id=bid,
                    signatures=sigs_objs)
    lanes, sigs = [], []
    for i in range(n):
        vi = i % n_vals
        msg = commit.vote_sign_bytes(long_chain, i)
        sig = ref.sign(seeds[vi], msg)
        sigs_objs[i].signature = sig
        lanes.append(vi)
        sigs.append(sig)
    sb = CommitSignBatch(long_chain, commit, list(range(n)))
    assert int(sb.split.max()) == 2  # two-byte outer varint on device
    e = ex.ExpandedKeys(pubs)
    got = e.verify_structured(lanes, sb, sigs)
    assert bool(np.asarray(got).all())


@pytest.mark.parametrize("lanes_n, mesh", [(48, False), (2100, True)])
def test_structured_avals_are_what_a_launch_hands_the_program(
        lanes_n, mesh):
    """structured_phases() compiles from shapes alone: they must be the
    shapes, dtypes and placements _launch_structured would pass for a
    real batch, below and above the lane-sharding threshold."""
    pubs, commit, lanes, sigs, _ = _mk(n_lanes=lanes_n)
    sb = CommitSignBatch(CHAIN, commit, list(range(len(lanes))))
    e = object.__new__(ex.ExpandedKeys)     # no tables: host side only
    e.pubkeys, e.sharded = tuple(pubs), False
    e.mesh = ex.tv._mesh() if mesh else None
    e.akeys = np.zeros((len(pubs), 32), np.uint8)
    e.key_ok = np.ones(len(pubs), bool)
    e.tables = np.zeros((len(pubs), 4), np.int32)
    idx, fields, _wf, width, _slots = e._prepare_structured(
        lanes, sb, sigs)
    idx, fields, btab = e._shard_args(idx, fields, repl_keys=e._S_REPL)
    args = dict(idx=idx, akeys=e.akeys, key_ok=e.key_ok, atab=e.tables,
                btab=btab, **fields)
    avals = e._structured_avals(idx.shape[0])
    assert width == 192 and set(avals) == set(args)
    for k, v in args.items():
        assert (avals[k].shape, avals[k].dtype) == (v.shape, v.dtype), k
        assert avals[k].sharding == getattr(v, "sharding", None), k
    assert (avals["idx"].sharding is not None) == mesh


def test_structured_phases_of_the_shape_launched_last(monkeypatch):
    """The instruction -> phase map is read off the executable the
    launches run, at the (24 keys, bucket 64, width 192) shape the
    tests above launch; no launch keeps anything for it."""
    from tendermint_tpu.crypto.tpu import verify as tv

    pubs, commit, lanes, sigs, expect = _mk()
    sb = CommitSignBatch(CHAIN, commit, list(range(len(lanes))))
    monkeypatch.setattr(ex, "_CACHE", type(ex._CACHE)())
    with pytest.raises(ValueError):
        ex.structured_phases()
    e = ex.ExpandedKeys(pubs)
    ex._CACHE[b"k"] = e
    assert list(e.verify_structured(lanes, sb, sigs)) == expect
    # the launch's own executable serves it: nothing compiles, nothing
    # is loaded from the persistent cache (JAX times both as one event)
    from jax import monitoring

    compiles = []

    def on(event, secs, **kw):
        if event.endswith("backend_compile_duration"):
            compiles.append(secs)

    monitoring.register_event_duration_secs_listener(on)
    try:
        phase_of = ex.structured_phases()
    finally:
        monitoring.unregister_event_duration_listener(on)
    assert compiles == []
    assert set(phase_of.values()) <= set(tv.PHASES)
    assert {tv.PHASE_ASSEMBLE, tv.PHASE_SHA512, tv.PHASE_MSM} <= set(
        phase_of.values())


def test_structured_launches_are_keyed_by_their_names_alone():
    """Under what a structured launch is lowered with, a module holds
    the operations' names (scope and primitive) and no file or line:
    the cache key that includes them survives a line shift, and a
    loaded executable carries the names structured_phases() reads."""
    import jax
    import jax.numpy as jnp

    from tendermint_tpu.crypto.tpu import verify as tv

    def scoped(x):
        with jax.named_scope(tv.PHASE_MSM):
            return x * 2

    x = jnp.ones(4)
    with ex._phase_names_in_key():
        text = jax.jit(scoped).lower(x).as_text(debug_info=True)
        assert jax.config.jax_compilation_cache_include_metadata_in_key
    assert f'loc("jit(scoped)/{tv.PHASE_MSM}/mul")' in text
    assert ".py" not in text
    assert not jax.config.jax_compilation_cache_include_metadata_in_key
    assert ".py" in jax.jit(lambda v: scoped(v)).lower(x).as_text(
        debug_info=True)


@pytest.mark.slow
def test_merged_window_batch():
    """Fast-sync window shape: several commits (distinct heights /
    block ids), one MergedSignBatch, one structured launch — verdicts
    match the oracle per lane, and a tampered block's lanes fail
    without affecting neighbors. Byte-identity of the merged
    reassembly is asserted for every lane."""
    from tendermint_tpu.types.sign_batch import MergedSignBatch

    n_vals = 24
    seeds = [hashlib.sha256(b"sv%d" % i).digest() for i in range(n_vals)]
    pubs = [ref.public_key_from_seed(s) for s in seeds]
    batches, lanes_all, sigs_all, expect = [], [], [], []
    for b in range(3):
        bid = BlockID(hash=bytes([b] * 32),
                      part_set_header=PartSetHeader(1, bytes(32)))
        cs = [CommitSig(BlockIDFlag.COMMIT, bytes([i] * 20),
                        10**18 + b * 1000 + i, b"")
              for i in range(16)]
        commit = Commit(height=100 + b, round=0, block_id=bid,
                        signatures=cs)
        slots = list(range(16))
        for i in slots:
            vi = (b * 16 + i) % n_vals
            msg = commit.vote_sign_bytes(CHAIN, i)
            sig = ref.sign(seeds[vi], msg)
            ok = True
            if b == 1 and i == 4:
                sig = ref.sign(seeds[(vi + 1) % n_vals], msg)  # forged
                ok = False
            cs[i].signature = sig
            lanes_all.append(vi)
            sigs_all.append(sig)
            expect.append(ok)
        batches.append(CommitSignBatch(CHAIN, commit, slots))
    merged = MergedSignBatch(batches)
    want_bytes = merged.materialize()
    for i in range(len(merged)):
        assert merged.host_assemble(i) == want_bytes[i], f"lane {i}"
    e = ex.ExpandedKeys(pubs)
    got = e.verify_structured(lanes_all, merged, sigs_all)
    assert list(got) == expect


def test_vote_batch_structured_verdicts(monkeypatch):
    """Vote micro-batch through ValidatorSet._batch_verify_lanes with
    a VoteSignBatch (the scheduler's structured route): verdicts match
    per-lane expectations incl. a tampered-timestamp vote and a
    cross-round mix. Uses the same (valset=24, bucket=64) shapes as
    the tests above, so no fresh kernel compiles."""
    import tendermint_tpu.types.validator_set as vs_mod
    from tendermint_tpu.crypto.ed25519 import Ed25519PubKey
    from tendermint_tpu.types.sign_batch import VoteSignBatch
    from tendermint_tpu.types.validator import Validator
    from tendermint_tpu.types.validator_set import ValidatorSet
    from tendermint_tpu.types.vote import Vote, VoteType

    monkeypatch.setattr(vs_mod, "_EXPAND_MIN", 4)
    n_vals = 24
    seeds = [hashlib.sha256(b"sv%d" % i).digest() for i in range(n_vals)]
    pubs = [Ed25519PubKey(ref.public_key_from_seed(s))
            for s in seeds]
    by_addr = {pubs[i].address(): seeds[i] for i in range(n_vals)}
    vals = ValidatorSet([Validator(address=p.address(), pub_key=p,
                                   voting_power=5) for p in pubs])
    bid = BlockID(hash=bytes(range(32)),
                  part_set_header=PartSetHeader(1, bytes(32)))
    votes, sigs, lanes, expect = [], [], [], []
    for i, v in enumerate(vals.validators):
        for r in (0, 1):  # two rounds in one micro-batch
            vote = Vote(type=VoteType.PREVOTE, height=9, round=r,
                        block_id=bid, timestamp=10**18 + i * 7 + r,
                        validator_address=v.address,
                        validator_index=i)
            sig = ref.sign(by_addr[v.address],
                           vote.sign_bytes(CHAIN))
            ok = True
            if i == 3 and r == 1:
                vote.timestamp += 1  # signed bytes != carried ts
                ok = False
            vote.signature = sig
            votes.append(vote)
            sigs.append(sig)
            lanes.append(i)
            expect.append(ok)
    sb = VoteSignBatch(CHAIN, votes)
    all_ok, verdicts = vals._batch_verify_lanes(lanes, sb, sigs)
    assert list(verdicts) == expect and not all_ok


# ------------------------------- the two table reads, side by side


_RUN_KEYS = 150     # not a multiple of a bucket, nor of _BLOCK_KEYS


@pytest.fixture(scope="module")
def run_keys():
    """150 keys' tables, key 77 one that does not decompress (key_ok
    False), and the seeds of the others."""
    seeds = [hashlib.sha256(b"run%d" % i).digest()
             for i in range(_RUN_KEYS)]
    pubs = [ref.public_key_from_seed(s) for s in seeds]
    bad = next(b for b in (bytes([v]) + bytes(31) for v in range(2, 99))
               if ref.decompress(b) is None)
    pubs[77] = bad
    return ex.ExpandedKeys(pubs), seeds


def _run_commit(seeds, keys, spoil=None):
    """A Commit whose slot i is key keys[i]'s vote (the lanes of a
    launch in the set's order), signed; `spoil` {position: how}."""
    bid = BlockID(hash=bytes(range(32)),
                  part_set_header=PartSetHeader(2, bytes(32)))
    css = [CommitSig(BlockIDFlag.NIL if k % 7 == 3 else BlockIDFlag.COMMIT,
                     bytes([k % 256] * 20), 10**18 + 977 * k, b"")
           for k in keys]
    commit = Commit(height=977, round=1, block_id=bid, signatures=css)
    for i, k in enumerate(keys):
        sig = ref.sign(seeds[k], commit.vote_sign_bytes(CHAIN, i))
        how = (spoil or {}).get(i)
        if how == "forged":
            sig = sig[:7] + bytes([sig[7] ^ 1]) + sig[8:]
        elif how == "malformed":
            sig = sig[:63]
        css[i].signature = sig
    sb = CommitSignBatch(CHAIN, commit, list(range(len(keys))))
    return sb, [c.signature for c in css]


def _absent(rng_seed: int, share: float):
    rng = np.random.default_rng(rng_seed)
    gone = rng.choice(_RUN_KEYS, int(round(share * _RUN_KEYS)),
                      replace=False)
    return [k for k in range(_RUN_KEYS) if k not in set(gone.tolist())]


# (the keys of the launch in order, {position: how spoiled})
_RUNS = {
    "whole_set": (list(range(_RUN_KEYS)), {}),
    "absent_3pc": (_absent(43, 0.03), {}),
    "prefix": (list(range(101)), {}),
    "base_above_0": (list(range(40, 140)), {}),
    "ends_at_last_key": (list(range(50, _RUN_KEYS)), {}),
    "gaps_forged_malformed_bad_key": (
        [k for k in range(36, 150) if k % 9], {5: "forged",
                                               11: "malformed"}),
}


@pytest.mark.parametrize("case, form", [
    (c, "structured") for c in sorted(_RUNS)] + [
    # the bytes path shares the slots and the core: one bucket's cases
    ("ends_at_last_key", "bytes"),
    ("gaps_forged_malformed_bad_key", "bytes")])
def test_in_order_read_gives_the_gathers_verdicts(run_keys, case, form):
    """The program that reads the rows as they lie and the one that
    gathers them, on the SAME launch (an in-order launch's arrays are
    a legal gather launch too): the whole verdict vectors equal, lane
    for lane, empty and clipped slots included, and what the caller
    reads is the oracle's."""
    e, seeds = run_keys
    keys, spoil = _RUNS[case]
    sb, sigs = _run_commit(seeds, keys, spoil)
    if form == "structured":
        idx, fields, wf, width, slots = e._prepare_structured(
            keys, sb, sigs)
        both = [np.asarray(e._launch_structured(idx, fields, width, io))
                for io in (True, False)]
        got = e.verify_structured(keys, sb, sigs)
    else:
        idx, packed, wf, slots = e._prepare(keys, sb.materialize(), sigs)
        both = [np.asarray(e._launch(idx, packed, io))
                for io in (True, False)]
        got = e.verify(keys, sb.materialize(), sigs)
    assert slots is not None and len(both[0]) == ex.ExpandedKeys._bucket(
        len(keys))
    base = keys[0] // ex._BLOCK_KEYS * ex._BLOCK_KEYS
    assert list(slots) == [k - base for k in keys]
    assert list(idx) == [min(base + i, _RUN_KEYS - 1)
                         for i in range(len(idx))]
    assert both[0].tolist() == both[1].tolist()
    expect = [k != 77 and spoil.get(i) is None
              for i, k in enumerate(keys)]
    assert list(both[0][slots] & wf) == expect == list(got)
    # no slot without a signature yields a verdict
    empty = np.ones(len(both[0]), bool)
    empty[slots] = False
    assert not both[0][empty].any()
    if spoil:
        refused = [i for i, ok in enumerate(got) if not ok]
        assert refused == sorted(
            list(spoil) + [keys.index(77)]) and 77 in keys


def _stub(n_keys: int, mesh=None):
    e = _tableless_keys(n_keys)
    e.mesh = mesh
    return e


@pytest.mark.parametrize("idx, lanes, base", [
    (np.arange(300), None, 0),                      # the set, whole
    (np.arange(40, 300), None, 32),                 # from its block
    (np.array([3, 4, 9, 100, 127]), None, 0),       # gaps, one bucket
    (np.arange(37, 160), None, 32),                 # 37..159 from 32
    (np.arange(37, 161), None, None),               # one key too far
    (np.arange(5, 45), 128, 0),                     # the live shape
    (np.arange(60, 170), 128, None),                # 32..169 > 128 lanes
    (np.array([0, 1, 2, 2, 3]), None, None),        # a repeat
    (np.array([4, 3, 2, 1]), None, None),           # descending
    (np.random.default_rng(7).permutation(200), None, None),
    (np.arange(0, 3000, 25), None, None),           # sparse: span >> n
    (np.arange(90), 100, None),                     # lanes off a slab
])
def test_rule_that_picks_the_table_read(idx, lanes, base):
    idx = idx.astype(np.int32)
    bucket = lanes or ex.ExpandedKeys._bucket(len(idx))
    assert _stub(3000)._in_order_base(idx, bucket) == base


def test_lanes_spread_over_devices_keep_the_gather():
    idx = np.arange(2048, dtype=np.int32)
    assert _stub(3000)._in_order_base(idx, 2048) == 0
    assert _stub(3000, ex.tv._mesh())._in_order_base(idx, 2048) is None
    assert _stub(3000, ex.tv._mesh())._in_order_base(idx[:1024],
                                                    1024) == 0
    sharded = _stub(3000)
    sharded.sharded = True
    assert sharded._in_order_base(idx, 2048) is None


def test_ledger_record_says_which_read_ran(run_keys):
    from tendermint_tpu.crypto.tpu import ledger

    e, seeds = run_keys
    keys = _RUNS["ends_at_last_key"][0]
    sb, sigs = _run_commit(seeds, keys)
    assert list(e.verify_structured(keys, sb, sigs)) == [
        k != 77 for k in keys]
    r = ledger.snapshot()[-1]
    assert (r["kernel"], r["rows"], r["lanes"], r["capacity"]) == (
        "structured", "in_order", 100, 128)
    back = keys[::-1]
    sb, sigs = _run_commit(seeds, back)
    assert list(e.verify_structured(back, sb, sigs)) == [
        k != 77 for k in back]
    r = ledger.snapshot()[-1]
    assert (r["rows"], r["lanes"], r["capacity"]) == ("gathered", 100, 128)
    roll = ledger.rollup(ledger.snapshot()[-2:])["workloads"]
    assert [w["rows_lanes"] for w in roll.values()] == [
        {"in_order": 100, "gathered": 100}]


def test_phases_of_an_in_order_launch(run_keys, monkeypatch):
    """structured_phases() after an in-order launch maps THAT
    program, from the launch's own executable: nothing compiles."""
    from jax import monitoring

    e, seeds = run_keys
    keys = _RUNS["prefix"][0]
    sb, sigs = _run_commit(seeds, keys)
    monkeypatch.setattr(ex, "_CACHE", type(ex._CACHE)({b"k": e}))
    monkeypatch.setattr(ex.tv, "_COMPILED_SHAPES", {})
    assert e.verify_structured(keys, sb, sigs).sum() == len(keys) - 1
    assert next(reversed(ex.tv._COMPILED_SHAPES)) == (
        "structured", 128, 192, "in_order")
    compiles = []

    def on(event, secs, **kw):
        if event.endswith("backend_compile_duration"):
            compiles.append(secs)

    monitoring.register_event_duration_secs_listener(on)
    try:
        phase_of = ex.structured_phases()
    finally:
        monitoring.unregister_event_duration_listener(on)
    assert compiles == []
    assert ex.tv.PHASE_GATHER in set(phase_of.values())


def test_load_structured_loads_both_reads(run_keys, monkeypatch):
    e, _ = run_keys
    monkeypatch.setattr(ex, "_LIVE_LANES", set())
    monkeypatch.setattr(ex.tv, "_COMPILED_SHAPES", {})
    assert e.load_structured(128) == 2
    assert set(ex.tv._COMPILED_SHAPES) == {
        ("structured", 128, 192), ("structured", 128, 192, "in_order")}
    assert e.load_structured(128) == 0


# ---------------------------------------------- the assembly alone


_SECS = 1_753_928_000
_NANOS = (1, 300, 70_000, 9_000_000, 999_999_999)   # varints of 1-5 B
_ALL_TS = ((0, 10**9, _SECS * 10**9) + _NANOS
           + tuple(_SECS * 10**9 + v for v in _NANOS))


def _sign_batch(chain, ts, n=40, nil_every=0, height=977, total=2):
    """A CommitSignBatch of n unsigned slots cycling through `ts`
    (the assembly never reads a signature)."""
    bid = BlockID(hash=bytes(range(32)),
                  part_set_header=PartSetHeader(total, bytes(32)))
    css = [CommitSig(
        BlockIDFlag.NIL if nil_every and i % nil_every == 1
        else BlockIDFlag.COMMIT,
        bytes([i % 256] * 20), ts[i % len(ts)], b"") for i in range(n)]
    commit = Commit(height=height, round=1, block_id=bid, signatures=css)
    return CommitSignBatch(chain, commit, list(range(n)))


def _merged_32_groups():
    from tendermint_tpu.types.sign_batch import MergedSignBatch

    return MergedSignBatch([
        _sign_batch(CHAIN, _ALL_TS[b % 5:], n=8, nil_every=2,
                    height=100 + b) for b in range(16)])


# id -> (batch, the outer-varint lengths, groups and width it must
# show, how it is run): every layout class _build_patches can emit
_ASSEMBLE_CASES = {
    "ts0-varint1": (lambda: _sign_batch("c", (0,)), {1}, 1, 192, ""),
    "ts0-varint2": (lambda: _sign_batch("c" * 50, (0,)), {2}, 1, 192, ""),
    "secs-only-varint1": (
        lambda: _sign_batch("c", (10**9, _SECS * 10**9)), {1}, 1, 192, ""),
    "secs-only-varint2": (
        lambda: _sign_batch("c" * 50, (10**9, _SECS * 10**9)),
        {2}, 1, 192, ""),
    **{f"nanos-{k + 1}B": (
        lambda v=v: _sign_batch("c", (v,)), {1}, 1, 192, "")
       for k, v in enumerate(_NANOS)},
    "secs+nanos-varint2": (
        lambda: _sign_batch("c" * 50, _ALL_TS[-5:]), {2}, 1, 192, ""),
    "mixed-varint": (
        lambda: _sign_batch("c" * 24, _ALL_TS), {1, 2}, 1, 192, ""),
    "nil-second-group": (
        lambda: _sign_batch("c" * 24, _ALL_TS, nil_every=3),
        {1, 2}, 2, 192, ""),
    "merged-32-groups": (_merged_32_groups, {1}, 32, 192, ""),
    "width-448": (
        lambda: _sign_batch("c" * 62, _ALL_TS, total=1 << 31),
        {2}, 1, 448, ""),
    "short-at-width-448": (
        lambda: _sign_batch("c", _ALL_TS, nil_every=3), {1}, 2, 192,
        "wide"),
    "padded-tail": (
        lambda: _sign_batch("c" * 24, _ALL_TS, n=130, nil_every=7),
        {1, 2}, 2, 192, ""),
    "vmap": (
        lambda: _sign_batch("c" * 24, _ALL_TS, n=130, nil_every=7),
        {1, 2}, 2, 192, "vmap"),
}


def _tableless_keys(n_keys: int, tables=None):
    """An ExpandedKeys of n_keys pubkeys on one device with no tables
    built: the host side (_prepare_structured, _structured_avals)."""
    e = object.__new__(ex.ExpandedKeys)
    e.pubkeys, e.sharded, e.mesh = (bytes(32),) * n_keys, False, None
    e.akeys = np.zeros((n_keys, 32), np.uint8)
    e.key_ok = np.ones(n_keys, bool)
    e.tables = tables
    return e


def _sha_padded(msg: bytes, width: int):
    """What follows R || A in the hash's input, padded, in numpy's
    terms: msg, 0x80, zeros, the 16-byte big-endian bit length at the
    end of the last block; and the number of 128-byte blocks."""
    total = 64 + len(msg)
    nblocks = (total + 17 + 127) // 128
    buf = np.zeros(width, np.uint8)
    buf[:len(msg)] = np.frombuffer(msg, np.uint8)
    buf[len(msg)] = 0x80
    end = nblocks * 128 - 64
    buf[end - 16:end] = np.frombuffer(
        (total * 8).to_bytes(16, "big"), np.uint8)
    return buf.tobytes(), nblocks


@pytest.mark.parametrize("case", list(_ASSEMBLE_CASES))
def test_assembled_bytes_equal_host_assemble(case):
    """assemble_core() alone (no curve work): every real lane's words
    are the bytes of host_assemble(i) with the SHA-512 pad, and its
    block count, for each layout class."""
    import jax

    build, splits, groups, width, how = _ASSEMBLE_CASES[case]
    sb = build()
    n = len(sb)
    _idx, f, _wf, got_width, _slots = _tableless_keys(
        1)._prepare_structured(
        [0] * n, sb, [bytes(64)] * n)
    assert set(sb.split.tolist()) == splits
    assert sb.pre.shape[0] == groups and got_width == width
    assert f["patch"].shape[0] == ex.ExpandedKeys._bucket(n) >= n
    if how == "wide":
        width = 448
    templates = (f["pre"], f["pre_len"], f["suf"], f["suf_len"])
    lanes = (f["patch"], f["split"], f["patch_len"], f["group"])
    assemble = ex.assemble_core()
    if how == "vmap":   # the sharded form: lanes split over devices
        lanes = tuple(a.reshape((4, -1) + a.shape[1:]) for a in lanes)
        words, nblocks = jax.jit(jax.vmap(
            lambda *per: assemble(*templates, *per, width)))(*lanes)
        words = np.concatenate(list(np.asarray(words)), axis=2)
        nblocks = np.asarray(nblocks).reshape(-1)
    else:
        words, nblocks = jax.jit(assemble, static_argnums=8)(
            *templates, *lanes, width)
    assert words.shape == (width // 8, 2, f["patch"].shape[0])
    got = np.asarray(words).transpose(2, 0, 1).astype(">u4")
    full = sb.materialize()
    for i in range(n):
        assert sb.host_assemble(i) == full[i]
        want, want_blocks = _sha_padded(full[i], width)
        assert got[i].tobytes() == want, f"lane {i}"
        assert int(nblocks[i]) == want_blocks, f"lane {i}"


def test_no_per_element_gather_in_the_assembly():
    """The program pin: `_skernel` as lowered holds ONE gather of more
    indices than there are lanes, the comb-table rows (69 a lane).
    Row gathers by `idx` or `group` take a lane's worth; an (N, width)
    index plane under ed25519.assemble, which cost 60 % of the
    10,240-lane kernel, cannot come back unseen. (Over the whole
    program, not the scope alone: a gather lowered inside a shared
    private function, as jnp.take's is, has no scope in its location.)"""
    import re

    from tendermint_tpu.crypto.tpu import verify as tv

    keys, lanes = 24, 128
    e = _tableless_keys(keys, np.zeros(
        (keys * ex._WINDOWS * ex._ENTRIES, ex._ROW), np.int32))
    text = ex._skernel().lower(
        width=192, **e._structured_avals(lanes)).as_text(debug_info=True)
    assert f"/{tv.PHASE_ASSEMBLE}/" in text
    index_dims = re.findall(
        r'"stablehlo\.gather".*?: \(tensor<[^>]*>, tensor<([\dx]+)xi32>\)',
        text)
    counts = [int(np.prod([int(d) for d in dims.split("x")]))
              for dims in index_dims]
    assert lanes in counts                      # akeys[idx], key_ok[idx]
    assert sorted(c for c in counts if c > lanes) == [ex._WINDOWS * lanes]
