"""Property tests: JAX limb field arithmetic vs Python big-int ground truth.

Parametrized over both representations (crypto/tpu/fieldsel.py):
  * field      — 22 x 12-bit non-negative int32 limbs
  * field_f32  — 32 x 8-bit signed float32 limbs (exactness relies on
                 every value staying under 2^24; the adversarial
                 all-max patterns here drive exactly those bounds)
"""

import numpy as np
import pytest

from tendermint_tpu.crypto.tpu import field as field_i32
from tendermint_tpu.crypto.tpu import field_f32

P = field_i32.P
RNG = np.random.default_rng(1234)


@pytest.fixture(params=["i32", "f32"], ids=["i32", "f32"])
def fe(request):
    return field_i32 if request.param == "i32" else field_f32


def check_bound(fe, out, what):
    """REDUCED closure: non-negative for i32, symmetric for f32."""
    lo = -(fe.REDUCED_BOUND - 1) if fe.SIGNED else 0
    assert out.max() < fe.REDUCED_BOUND and out.min() >= lo, \
        f"{what} broke REDUCED bound [{lo}, {fe.REDUCED_BOUND})"


def rand_elems(fe, n, bound=None):
    """Random REDUCED limb batch (NLIMB, n) + matching Python ints."""
    bound = bound or fe.REDUCED_BOUND
    lo = -(bound - 1) if fe.SIGNED else 0
    limbs = RNG.integers(lo, bound, size=(fe.NLIMB, n), dtype=np.int64)
    vals = fe.from_limbs(limbs)
    return limbs.astype(np.asarray(fe.to_limbs(0)).dtype), vals


def adversarial_elems(fe):
    """Near-max patterns: all limbs at the REDUCED bound (both signs
    when the rep is signed), zeros, p, max representable, etc."""
    max_rep = (1 << (fe.BITS * fe.NLIMB)) - 1
    cols = [
        np.full(fe.NLIMB, fe.REDUCED_BOUND - 1),
        np.zeros(fe.NLIMB),
        np.full(fe.NLIMB, fe.MASK),
        fe.to_limbs(P),
        fe.to_limbs(2 * P) if 2 * P <= max_rep else fe.to_limbs(P - 2),
        fe.to_limbs(P - 1),
        fe.to_limbs(P + 1),
        fe.to_limbs(1),
        fe.to_limbs(max_rep),
        fe.to_limbs(19),
    ]
    if fe.SIGNED:
        cols.append(np.full(fe.NLIMB, -(fe.REDUCED_BOUND - 1)))
        alt = np.full(fe.NLIMB, fe.REDUCED_BOUND - 1)
        alt[::2] *= -1
        cols.append(alt)
    limbs = np.stack(cols, axis=1)
    return (limbs.astype(np.asarray(fe.to_limbs(0)).dtype),
            fe.from_limbs(limbs))


def test_to_from_limbs_roundtrip(fe):
    max_rep = (1 << (fe.BITS * fe.NLIMB)) - 1
    for v in [0, 1, 19, P - 1, P, P + 1, 2**255 - 1, max_rep]:
        assert fe.from_limbs(fe.to_limbs(v)) == v


@pytest.mark.parametrize("op,pyop", [("add", lambda a, b: a + b), ("sub", lambda a, b: a - b)])
def test_add_sub(fe, op, pyop):
    a_l, a_v = rand_elems(fe, 64)
    b_l, b_v = rand_elems(fe, 64)
    out = np.asarray(getattr(fe, op)(a_l, b_l))
    check_bound(fe, out, op)
    for got, av, bv in zip(fe.from_limbs(out), a_v, b_v):
        assert got % P == pyop(av, bv) % P


def test_mul_random(fe):
    a_l, a_v = rand_elems(fe, 128)
    b_l, b_v = rand_elems(fe, 128)
    out = np.asarray(fe.mul(a_l, b_l))
    check_bound(fe, out, "mul")
    for got, av, bv in zip(fe.from_limbs(out), a_v, b_v):
        assert got % P == (av * bv) % P


def test_mul_adversarial(fe):
    a_l, a_v = adversarial_elems(fe)
    # all pairs
    n = a_l.shape[1]
    ii, jj = np.meshgrid(np.arange(n), np.arange(n), indexing="ij")
    aa = a_l[:, ii.ravel()]
    bb = a_l[:, jj.ravel()]
    out = np.asarray(fe.mul(aa, bb))
    check_bound(fe, out, "mul")
    got = fe.from_limbs(out)
    for idx, (i, j) in enumerate(zip(ii.ravel(), jj.ravel())):
        assert got[idx] % P == (a_v[i] * a_v[j]) % P


def test_sqr_adversarial(fe):
    a_l, a_v = adversarial_elems(fe)
    out = np.asarray(fe.sqr(a_l))
    check_bound(fe, out, "sqr")
    for got, v in zip(fe.from_limbs(out), a_v):
        assert got % P == (v * v) % P


def test_sub_never_negative_intermediate():
    # i32 rep only: max b against min a — the bias must keep every
    # limb non-negative (the f32 rep is signed by design).
    fe = field_i32
    a = np.zeros((fe.NLIMB, 1), np.int32)
    b = np.full((fe.NLIMB, 1), fe.REDUCED_BOUND - 1, np.int32)
    out = np.asarray(fe.sub(a, b))
    assert out.min() >= 0
    assert fe.from_limbs(out)[0] % P == (0 - fe.from_limbs(b)[0]) % P


def test_canonical(fe):
    a_l, a_v = adversarial_elems(fe)
    out = np.asarray(fe.canonical(a_l))
    for got, v in zip(fe.from_limbs(out), a_v):
        assert got == v % P
        assert 0 <= got < P
    r_l, r_v = rand_elems(fe, 64)
    out = np.asarray(fe.canonical(r_l))
    for got, v in zip(fe.from_limbs(out), r_v):
        assert got == v % P


def test_canonical_signed_edges():
    """f32 rep: values that stress the fold-carry convergence proof —
    small negatives (borrow ripples), +/-1 around 0 and p, and the
    all-negative-max pattern whose value is about -2.7 * 2^256."""
    fe = field_f32
    cases = [-1, -19, -38, -39, 1 - (1 << 256), P - 1, 1, 0]
    vals = list(cases)
    cols = [None] * len(vals)
    # build signed limb decompositions exactly: v = sum limb_i 2^(8i)
    for k, v in enumerate(vals):
        x = v
        limbs = np.zeros(fe.NLIMB, np.float64)
        for i in range(fe.NLIMB):
            r = x % 256 if i < fe.NLIMB - 1 else x
            if i < fe.NLIMB - 1:
                limbs[i] = r
                x = (x - r) // 256
            else:
                limbs[i] = x
        assert abs(limbs).max() < (1 << 22), "edge case fits f32 limbs"
        cols[k] = limbs.astype(np.float32)
    a = np.stack(cols, axis=1)
    out = np.asarray(fe.canonical(a))
    for got, v in zip(fe.from_limbs(out), vals):
        assert got == v % P, f"canonical({v}) wrong"


def test_eq_and_is_zero(fe):
    one = fe.splat(1, 4)
    p_plus_1 = fe.splat(P + 1, 4)
    assert np.asarray(fe.eq(one, p_plus_1)).all(), "1 != p+1 mod p?"
    assert np.asarray(fe.is_zero(fe.splat(P, 3))).all()
    assert not np.asarray(fe.is_zero(fe.splat(1, 3))).any()


def test_parity(fe):
    # parity is of the canonical representative: p+1 ≡ 1 -> odd
    assert np.asarray(fe.parity(fe.splat(P + 1, 2)))[0] == 1
    assert np.asarray(fe.parity(fe.splat(P, 2)))[0] == 0
    assert np.asarray(fe.parity(fe.splat(4, 2)))[0] == 0


def test_pow_2_252_m3(fe):
    a_l, a_v = rand_elems(fe, 16)
    out = fe.from_limbs(np.asarray(fe.pow_2_252_m3(a_l)))
    e = (1 << 252) - 3
    for got, v in zip(out, a_v):
        assert got % P == pow(v % P, e, P)


def test_neg(fe):
    a_l, a_v = rand_elems(fe, 32)
    out = fe.from_limbs(np.asarray(fe.neg(a_l)))
    for got, v in zip(out, a_v):
        assert got % P == (-v) % P


def test_mul_chain_stability(fe):
    """Repeated squaring keeps the REDUCED bound (no drift)."""
    a_l, a_v = rand_elems(fe, 8)
    x = a_l
    v = list(a_v)
    for _ in range(50):
        x = fe.sqr(x)
        v = [(t * t) % P for t in v]
    x = np.asarray(x)
    check_bound(fe, x, "sqr chain")
    for got, want in zip(fe.from_limbs(x), v):
        assert got % P == want


def test_carry_lookahead_matches_ripple():
    """The log-depth Kogge-Stone normalization must agree with the
    sequential ripple on every input in its precondition range
    (limbs <= 8190, carries binary), including long propagate chains
    (4095 runs) and generate-at-top patterns."""
    fe = field_i32
    cols = [
        np.full(fe.NLIMB, 4095),                 # all-propagate
        np.full(fe.NLIMB, 4096),                 # all-generate
        np.full(fe.NLIMB, 8190),                 # max precondition
        np.zeros(fe.NLIMB),
    ]
    chain = np.full(fe.NLIMB, 4095)
    chain[0] = 4096                              # carry ripples to top
    cols.append(chain)
    rng = np.random.default_rng(7)
    for _ in range(64):
        cols.append(rng.integers(0, 8191, fe.NLIMB))
    x = np.stack(cols, axis=1).astype(np.int32)
    want_l, want_c = (np.asarray(v) for v in fe._ripple22(x))
    got_l, got_c = (np.asarray(v) for v in fe._ks_norm(x))
    # _ripple22 carries multi-bit out of intermediate limbs only when
    # limbs exceed the binary range; within the precondition both must
    # agree exactly.
    assert (got_l == want_l).all()
    assert (got_c == want_c).all()


def test_f32_matches_i32_differential():
    """The two representations agree mul-for-mul on random inputs
    (beyond both agreeing with Python ints — catches from_limbs bugs)."""
    vals = [int(RNG.integers(0, 1 << 62)) * int(RNG.integers(0, 1 << 62))
            % P for _ in range(32)]
    vals += [0, 1, P - 1, P - 2, 2**255 - 20]
    n = len(vals)
    a32 = np.stack([field_i32.to_limbs(v) for v in vals], axis=1)
    af = np.stack([field_f32.to_limbs(v) for v in vals], axis=1)
    b32 = np.stack([field_i32.to_limbs(vals[(i + 7) % n])
                    for i in range(n)], axis=1)
    bf = np.stack([field_f32.to_limbs(vals[(i + 7) % n])
                   for i in range(n)], axis=1)
    m32 = field_i32.from_limbs(np.asarray(field_i32.canonical(
        field_i32.mul(a32, b32))))
    mf = field_f32.from_limbs(np.asarray(field_f32.canonical(
        field_f32.mul(af, bf))))
    assert m32 == mf


def _calls(jaxpr) -> list[str]:
    """Names of the jitted functions a jaxpr calls at its top level."""
    return [e.params["name"] for e in jaxpr.eqns
            if e.primitive.name in ("pjit", "jit")]


def test_ops_are_inline_unless_traced_under_as_calls(fe):
    """The default is the inlined form every kernel had; under
    as_calls() each operation is ONE call of its jitted self, traced
    once a shape, with the same result (PR 35)."""
    import jax

    a, _ = rand_elems(fe, 4)
    b, _ = rand_elems(fe, 4)

    def f(x, y):
        return fe.sub(fe.add(fe.mul(x, y), fe.sqr(x)), fe.neg(y))

    def jaxpr_of(fn, *args):
        # a function of its own each time: jax keeps a function's
        # trace by its shapes, whatever form it was traced in
        return jax.make_jaxpr(lambda *xs: fn(*xs))(*args).jaxpr

    inline = jaxpr_of(f, a, b)
    assert not {"mul", "sqr", "add", "sub", "neg"} & set(_calls(inline))
    with fe.as_calls():
        called = jaxpr_of(f, a, b)
        got = np.asarray(f(a, b))
    assert sorted(_calls(called)) == ["add", "mul", "neg", "sqr", "sub"]
    assert len(called.eqns) == 5 < len(inline.eqns)
    assert (got == np.asarray(f(a, b))).all()
    # and off again once the block is left, or when asked with False
    assert "neg" not in _calls(jaxpr_of(fe.neg, a))
    with fe.as_calls(False):
        assert "neg" not in _calls(jaxpr_of(fe.neg, a))


def test_sr25519_kernel_takes_the_field_as_calls():
    """54,034 top-level equations inlined, under 5,000 as calls: what
    a launch shape's first launch costs in tracing and lowering."""
    from helpers import sr_kernel_args

    from tendermint_tpu.crypto.tpu import sr_verify

    jaxpr = sr_verify._kernel().trace(
        **sr_kernel_args(128)).jaxpr.jaxpr
    assert len(jaxpr.eqns) < 5000
    assert {"mul", "sqr"} <= set(_calls(jaxpr))


@pytest.mark.parametrize("max_lanes,called", [(None, True), (0, False)],
                         ids=["small-bucket", "over-the-limit"])
def test_structured_kernel_takes_calls_up_to_its_lane_limit(
        monkeypatch, max_lanes, called):
    """A small structured launch is traced with the field as calls; one
    over `_CALLS_MAX_LANES` keeps the inlined program the commit of
    10,000 validators was measured on (its lowered module is the
    parent's, byte for byte)."""
    import jax

    from tendermint_tpu.crypto.tpu import expanded as ex
    from tendermint_tpu.crypto.tpu import verify as tv
    from tendermint_tpu.types.block import (
        BlockID, BlockIDFlag, Commit, CommitSig, PartSetHeader)
    from tendermint_tpu.types.sign_batch import CommitSignBatch

    if max_lanes is not None:
        monkeypatch.setattr(ex, "_CALLS_MAX_LANES", max_lanes)
    n, n_keys = 128, 8
    commit = Commit(
        height=7, round=0,
        block_id=BlockID(hash=b"\xab" * 32,
                         part_set_header=PartSetHeader(4, b"\xcd" * 32)),
        signatures=[CommitSig(BlockIDFlag.COMMIT, bytes(20),
                              1_753_928_000_000_000_000 + i, bytes(64))
                    for i in range(n)])
    keys = object.__new__(ex.ExpandedKeys)
    keys.pubkeys = tuple(bytes([i]) * 32 for i in range(n_keys))
    keys.sharded, keys.mesh = False, None
    lanes = [i % n_keys for i in range(n)]
    idx, fields, _, width, _slots = keys._prepare_structured(
        lanes, CommitSignBatch("form", commit, list(range(n))),
        [bytes(64)] * n)

    def spec(a, rows=None):
        a = np.asarray(a)
        return jax.ShapeDtypeStruct(
            a.shape if rows is None else (rows,) + a.shape[1:],
            jax.dtypes.canonicalize_dtype(a.dtype))

    btab = tv.b_comb_tables()
    # a jit of its own: the cached one keeps a shape's first trace
    jaxpr = ex._skernel.__wrapped__().trace(
        idx=spec(idx), width=width,
        akeys=spec(np.zeros((1, 32), np.uint8), n_keys),
        key_ok=spec(np.zeros(1, bool), n_keys),
        atab=spec(np.zeros((1, ex._ROW), btab.dtype),
                  n_keys * ex._WINDOWS * ex._ENTRIES),
        btab=spec(btab), **{k: spec(v) for k, v in fields.items()}
    ).jaxpr.jaxpr
    assert ("mul" in _calls(jaxpr)) is called
    assert (len(jaxpr.eqns) < 10_000) is called
