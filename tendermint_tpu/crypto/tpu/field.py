"""GF(2^255-19) arithmetic on batched int32 limb vectors.

Representation: a field element batch is an int32 array of shape
(22, N): limb i holds 12 bits of weight 2^(12*i) (264 bits total), batch
on the trailing axis. Values are *redundant* representatives: any
integer in [0, 2^266) congruent to the element mod p.

Bounds discipline (every op documents its contract; tests enforce it):

- REDUCED: every limb < 7700. `mul`/`sqr` require REDUCED inputs —
  then every schoolbook column is <= 22 * 7699^2 = 1.31e9 < 2^31, so
  int32 never overflows — and produce REDUCED output.
- `add`/`sub` accept REDUCED and produce REDUCED via one carry pass.
- `canonical` produces the unique representative in [0, p) with 12-bit
  limbs; used only for compares/parity (a few per verify, off the hot
  path).

The top-limb fold uses 2^264 = 2^9 * 19 (mod p): a carry c out of limb
21 re-enters as 19*c at bit 9, split as ((19c)&7)<<9 into limb 0 plus
(19c)>>3 into limb 1 so no intermediate exceeds int32. The &7 part is
why REDUCED is 7700, not 4096: limb 0 can sit at 4095 + 3584 + eps
after a single pass, and that is fine — the mul overflow bound has
~1.6x headroom over it.

Everything here is pure-functional jnp on int32 — no Python control
flow on data — so the whole verifier jits into one XLA program.

A verify kernel calls `add`, `sub`, `neg`, `mul` and `sqr` a few
hundred times: inlined, that is 54,000-81,000 equations to trace and
to lower for EVERY lane bucket (~10 s of tracing anywhere, 15-42 s of
`jaxpr_to_mlir_module` on the chip's host: PERF.md §6, PR 35). A kernel
traced under `as_calls()` gets them as calls of jitted functions, each
traced once a shape and lowered to one function a module (the sr25519
kernel: 3,856 top-level equations, its first launch ~5 s of Python for
~35). XLA inlines the calls before it optimises and the arithmetic is
the same, but its schedule is not: the sr25519 kernel runs as fast or
faster so (512 / 1,024 / 4,096 lanes), `jit_skernel` as fast at 1,024
and 2,048 lanes and 8-11 % SLOWER at 7,168 and 10,240 (my chip runs,
PR 35). So the form is the kernel's choice, and the default is inline.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools

import jax
import jax.numpy as jnp
import numpy as np

P = 2**255 - 19
NLIMB = 22
BITS = 12
MASK = (1 << BITS) - 1
# 2^(12*22) = 2^264 ≡ 19 * 2^9 (mod p)
FOLD = 19 << 9
SIGNED = False  # limbs are kept non-negative (see sub bias below)

_AS_CALLS = contextvars.ContextVar("field_ops_as_calls", default=False)


@contextlib.contextmanager
def as_calls(on: bool = True):
    """While a kernel's body is traced under this, the field operations
    it calls are calls of jitted functions (module docstring)."""
    token = _AS_CALLS.set(on)
    try:
        yield
    finally:
        _AS_CALLS.reset(token)


def _op(fn):
    """A field operation in both forms: inline, or under as_calls() a
    call of its jitted self."""
    call = jax.jit(fn)

    @functools.wraps(fn)
    def op(*args):
        return call(*args) if _AS_CALLS.get() else fn(*args)

    return op


def to_limbs(x: int) -> np.ndarray:
    """Python int -> (22,) int32 canonical limb vector. x must be < 2^264."""
    assert 0 <= x < 1 << (BITS * NLIMB)
    out = np.zeros(NLIMB, np.int32)
    for i in range(NLIMB):
        out[i] = x & MASK
        x >>= BITS
    return out


def from_limbs(limbs):
    """(K,) or (K, N) limb array -> Python int(s) — for tests/host."""
    arr = np.asarray(limbs)
    if arr.ndim == 1:
        return sum(int(arr[i]) << (BITS * i) for i in range(arr.shape[0]))
    return [
        sum(int(arr[i, n]) << (BITS * i) for i in range(arr.shape[0]))
        for n in range(arr.shape[1])
    ]


def splat(x: int, n: int) -> jnp.ndarray:
    """Broadcast a constant element across an N-batch."""
    return jnp.tile(jnp.asarray(to_limbs(x))[:, None], (1, n))


def limbs_from_bytes(byte_rows) -> jnp.ndarray:
    """(32, N) int32 byte rows (LE, top byte pre-masked) -> (22, N)
    12-bit limbs (static shift/mask rows; shared with scalar.py)."""
    from . import scalar as sc

    return sc.bytes_to_limbs(byte_rows, NLIMB)


# Bias for subtraction: 1024*p in a redundant representation whose every
# limb is >= 8189 > REDUCED bound, so (a + BIAS - b) is limb-wise
# non-negative for any REDUCED a, b. Derivation: canonical limbs of
# 1024p = 2^265 - 19456 are [1024, 4091, 4095*19, 8191 (incl. the 2^264
# bit)]; add 8192 to limbs 0..20 and subtract 2 from limbs 1..21
# (value-preserving redistribution).
def _make_sub_bias() -> np.ndarray:
    c = np.zeros(NLIMB, np.int64)
    v = 1024 * P
    for i in range(NLIMB):
        c[i] = v & MASK
        v >>= BITS
    c[21] += v << BITS  # 1024p = 2^265 - 19456: fold the 2^264 bit into limb 21
    b = c.copy()
    b[:21] += 8192
    b[1:] -= 2
    assert (b >= 8189).all() and b.max() < 1 << 15
    assert sum(int(b[i]) << (BITS * i) for i in range(NLIMB)) == 1024 * P
    return b.astype(np.int32)


_SUB_BIAS = _make_sub_bias()


def _fold_top(r: jnp.ndarray, ctop: jnp.ndarray) -> jnp.ndarray:
    """Fold a carry of weight 2^264 back in as 19*c at bit 9.

    Split across limbs 0 and 1 so the added values stay small:
    19*c * 2^9 = ((19c) & 7) * 2^9  +  ((19c) >> 3) * 2^12.
    Safe for ctop up to ~5e7.

    Written as a concatenate (not scatter/dynamic-update) so XLA fuses
    it into the surrounding elementwise graph instead of serializing
    buffer updates.
    """
    t = ctop * 19
    return jnp.concatenate(
        [
            (r[0] + ((t & 7) << 9))[None],
            (r[1] + (t >> 3))[None],
            r[2:],
        ],
        axis=0,
    )


def _pass22(x: jnp.ndarray) -> jnp.ndarray:
    """One parallel carry pass over 22 limbs with top fold.

    Arithmetic (signed) shift, so negative limbs borrow correctly.
    """
    c = x >> BITS
    r = x & MASK
    r = jnp.concatenate([r[:1], r[1:] + c[:-1]], axis=0)
    return _fold_top(r, c[-1])


REDUCED_BOUND = 7700


@_op
def add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """REDUCED + REDUCED -> REDUCED."""
    return _pass22(jnp.asarray(a) + jnp.asarray(b))


@_op
def sub(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """REDUCED - REDUCED -> REDUCED. Adds 1024p so limbs stay >= 0."""
    return _pass22(jnp.asarray(a) + jnp.asarray(_SUB_BIAS)[:, None] - jnp.asarray(b))


@_op
def neg(a: jnp.ndarray) -> jnp.ndarray:
    return _pass22(jnp.asarray(_SUB_BIAS)[:, None] - jnp.asarray(a))


@_op
def mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Field multiply. Inputs REDUCED (limbs < 7700); output REDUCED.

    Schoolbook over 22 limbs (columns <= 1.31e9 < 2^31), one exact-carry
    extension pass to 12-bit limbs, split fold of the top 22 limbs by
    2^264 ≡ 19*2^9, then three parallel carry passes. Bound chain is in
    the module docstring; tests drive randomized near-max patterns.
    """
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    # 22 row-broadcast multiplies (each (22, N) wide — vectorized over
    # the limb axis), shifted into the 43 columns by zero-padding, and
    # summed as a log-depth tree. No dynamic-update-slice chains: the
    # whole product graph is data-parallel adds XLA fuses freely.
    terms = [
        jnp.pad(a[i] * b, ((i, NLIMB - 1 - i), (0, 0)))
        for i in range(NLIMB)
    ]
    return _reduce43(_balanced_sum(terms))


def _balanced_sum(terms: list) -> jnp.ndarray:
    """Tree-shaped sum: log-depth adder chain instead of a serial one."""
    while len(terms) > 1:
        nxt = [terms[i] + terms[i + 1] for i in range(0, len(terms) - 1, 2)]
        if len(terms) & 1:
            nxt.append(terms[-1])
        terms = nxt
    return terms[0]


@_op
def sqr(a: jnp.ndarray) -> jnp.ndarray:
    """Dedicated squaring: ~half the limb products of a general mul.

    Columns c[i+j] = sum 2*a_i*a_j (i<j) + a_i^2. Overflow bound per
    column: an odd column has at most 11 doubled pairs (22*7699^2 =
    1.304e9); an even column has at most 10 doubled pairs plus one
    square term (21*7699^2 = 1.245e9); both < 2^31.
    """
    a = jnp.asarray(a)
    n = a.shape[-1]
    a2 = a + a
    # Diagonal a_i^2 terms land on even columns 0,2,..,42: interleave
    # with zero rows via a stack+reshape (one multiply, no scatter).
    diag = a * a  # (22, N)
    diag43 = jnp.stack([diag, jnp.zeros_like(diag)], axis=1).reshape(
        2 * NLIMB, n
    )[: 2 * NLIMB - 1]
    # Cross terms 2*a_i*a_j (i<j) shifted to column i+j.
    terms = [diag43]
    for i in range(NLIMB - 1):
        prod = a2[i] * a[i + 1 :]  # (21-i, N), columns 2i+1 .. i+21
        terms.append(jnp.pad(prod, ((2 * i + 1, NLIMB - 1 - i), (0, 0))))
    return _reduce43(_balanced_sum(terms))


def _reduce43(c: jnp.ndarray) -> jnp.ndarray:
    """(43, N) schoolbook columns (each < 2^31) -> REDUCED (22, N)."""
    # Pass 1: carry into 44 limbs; carries <= 1.31e9 >> 12 ≈ 3.2e5.
    cc = c >> BITS
    r = c & MASK
    r = jnp.concatenate([r[:1], r[1:] + cc[:-1], cc[-1:]], axis=0)  # (44, N)
    # Fold: limb (22+m) has weight 2^264 * 2^(12m) ≡ 19*2^9 * 2^(12m).
    # Split so nothing overflows: 19*hi * 2^9 = ((19h)&7)<<9 at limb m
    # plus (19h)>>3 at limb m+1; the m=21 spill (weight 2^264 again)
    # folds once more — it is small (<= ~1.5e7) by then.
    t = r[NLIMB:] * 19  # <= 19 * 3.3e5 ≈ 6.3e6
    t2 = (t[-1] >> 3) * 19
    hi_shift = t >> 3  # enters one limb up
    d0 = r[0] + ((t[0] & 7) << 9) + ((t2 & 7) << 9)
    d1 = r[1] + ((t[1] & 7) << 9) + hi_shift[0] + (t2 >> 3)
    rest = r[2:NLIMB] + ((t[2:] & 7) << 9) + hi_shift[1:-1]
    d = jnp.concatenate([d0[None], d1[None], rest], axis=0)
    # Three parallel passes: ~3e6 -> ~8.6e3 -> REDUCED.
    d = _pass22(d)
    d = _pass22(d)
    d = _pass22(d)
    return d


def _ripple22(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact sequential carry: limbs in [0, 4096) plus signed out-carry.

    Kept as the reference implementation for _ks_norm's differential
    tests; the kernels use the log-depth version below.
    """

    def step(carry, limb):
        v = limb + carry
        return v >> BITS, v & MASK

    out_c, limbs = jax.lax.scan(step, jnp.zeros(x.shape[-1], jnp.int32), x)
    return limbs, out_c


def carry_lookahead(g: jnp.ndarray, p: jnp.ndarray):
    """Kogge-Stone prefix over (generate, propagate) bool rows.

    g[i]: limb i emits a carry regardless of carry-in; p[i]: limb i
    emits a carry iff it receives one. Returns (carry-in per limb,
    top carry-out) in log2(K) parallel steps — the exact-normalization
    scans this replaces were 22-69 SEQUENTIAL lax.scan steps each, a
    measurable slice of the kernel's fixed per-launch latency.
    """
    G, Pp = g, p
    shift = 1
    k = g.shape[0]
    while shift < k:
        zg = jnp.zeros_like(G[:shift])
        G = G | (Pp & jnp.concatenate([zg, G[:-shift]], axis=0))
        Pp = Pp & jnp.concatenate([zg, Pp[:-shift]], axis=0)
        shift <<= 1
    cin = jnp.concatenate([jnp.zeros_like(G[:1]), G[:-1]], axis=0)
    return cin, G[-1]


def _ks_norm(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact carry normalization for limbs in [0, 2*4096): equivalent
    to _ripple22 (limbs -> [0, 4096) + out-carry in {0, 1}) but
    log-depth. Precondition: every limb <= 8190 and every
    (limb + carry-in) <= 8191, so per-limb carries are binary —
    callers establish this with one _pass22 first.
    """
    g = x >= 4096
    p = x >= 4095
    cin, cout = carry_lookahead(g, p)
    return (x + cin.astype(jnp.int32)) & MASK, cout.astype(jnp.int32)


def canonical(x: jnp.ndarray) -> jnp.ndarray:
    """Unique representative in [0, p) with 12-bit limbs. All
    log-depth: one parallel pass bounds limbs under 2*4096, then
    Kogge-Stone exact normalizations (5 steps each) replace the
    sequential ripples.
    """
    # REDUCED-ish input (< 7700): one pass -> limbs <= 4095 + 3584
    # (fold on limb 0) < 8190, carries binary from here on.
    l1 = _pass22(x)
    l1, c1 = _ks_norm(l1)
    l1 = _fold_top(l1, c1)  # limb0 += <=3584, limb1 += <=2 -> <= 8190
    # After this fold the value is < 2^264: the pass bounded the value
    # under ~1.001 * 2^264, so c1=1 implies the remainder was tiny and
    # re-adding 19*2^9 cannot reach 2^264 again -> top carry is 0.
    l2, _ = _ks_norm(l1)
    # Reduce 264 -> 255 bits: bits 255.. of limb 21 re-enter as *19,
    # split across limbs 0/1 to keep carries binary (19*hi <= 9709
    # added whole would break the <= 8190 precondition).
    hi19 = (l2[21] >> 3) * 19
    l2 = jnp.concatenate(
        [(l2[0] + (hi19 & MASK))[None],
         (l2[1] + (hi19 >> BITS))[None],
         l2[2:21], (l2[21] & 7)[None]], axis=0)
    l3, _ = _ks_norm(l2)  # value < 2^255 + 9728 < 2p
    # Conditional subtract: value >= p  iff  value + 19 >= 2^255.
    t = jnp.concatenate([(l3[0] + 19)[None], l3[1:]], axis=0)
    t4, _ = _ks_norm(t)
    ge = (t4[21] >> 3) > 0
    sub_p = jnp.concatenate([t4[:21], (t4[21] & 7)[None]], axis=0)
    return jnp.where(ge, sub_p, l3)


def eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Per-lane equality mod p -> (N,) bool."""
    return is_zero(sub(a, b))


def is_zero(a: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(canonical(a) == 0, axis=0)


def parity(a: jnp.ndarray) -> jnp.ndarray:
    """Low bit of the canonical representative -> (N,) int32 in {0,1}."""
    return canonical(a)[0] & 1


def nsquare(a: jnp.ndarray, n: int) -> jnp.ndarray:
    """a^(2^n) via n squarings (lax loop: compile body once)."""
    return jax.lax.fori_loop(0, n, lambda _, x: sqr(x), a)


def pow_2_252_m3(z: jnp.ndarray) -> jnp.ndarray:
    """z^(2^252 - 3) — the exponent for sqrt(u/v) in decompression.

    Standard ed25519 addition chain (11 multiplies + 252 squarings).
    """
    z2 = sqr(z)
    z9 = mul(sqr(sqr(z2)), z)
    z11 = mul(z9, z2)
    z_5_0 = mul(sqr(z11), z9)  # 2^5 - 1
    z_10_0 = mul(nsquare(z_5_0, 5), z_5_0)
    z_20_0 = mul(nsquare(z_10_0, 10), z_10_0)
    z_40_0 = mul(nsquare(z_20_0, 20), z_20_0)
    z_50_0 = mul(nsquare(z_40_0, 10), z_10_0)
    z_100_0 = mul(nsquare(z_50_0, 50), z_50_0)
    z_200_0 = mul(nsquare(z_100_0, 100), z_100_0)
    z_250_0 = mul(nsquare(z_200_0, 50), z_50_0)
    return mul(nsquare(z_250_0, 2), z)


# Curve constants (as Python ints; modules build jnp consts from these).
D = (-121665 * pow(121666, P - 2, P)) % P
D2 = (2 * D) % P
SQRT_M1 = pow(2, (P - 1) // 4, P)
