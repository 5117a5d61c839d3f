"""GF(2^255-19) arithmetic on batched float32 limb vectors.

Why float32: the TPU VPU executes f32 multiply/add at full rate but
EMULATES int32 multiply — measured on a v5e: ~0.59 T int32 mul-add/s
vs >10 T f32 op/s, an order-of-magnitude gap that made the int32
field kernel (field.py) multiply-bound (docs/PERF_NOTES.md). All
values here are small integers stored exactly in f32: every product
and every column sum is bounded below 2^24 — inside the 24-bit
mantissa — so the arithmetic is EXACT and bit-identical on any
IEEE-754 backend (TPU, CPU); there is no floating-point rounding
anywhere in this module.

Representation: a field element batch is a float32 array of shape
(32, N): limb i holds 8 bits of weight 2^(8i) (256 bits total), batch
on the trailing axis. Limbs are SIGNED redundant representatives: any
integer-valued limb vector with |limb| <= REDUCED bound whose value
(sum limb_i 2^(8i)) is congruent to the element mod p. Two structural
bonuses of 8-bit limbs: byte rows ARE limb rows (device unpack is a
dtype cast), and 4 coords x 32 limbs = 128 floats fill one TPU
(8, 128) tile row exactly (expanded.py table rows, zero pad waste).

Bounds discipline (mirrors field.py; tests drive all-max patterns):

- REDUCED: |limb| <= 680. `mul`/`sqr` require REDUCED inputs — then
  every schoolbook column is <= 32 * 680^2 = 14.8M < 2^24, so f32
  stays exact — and produce REDUCED output.
- `add`/`sub`/`neg` accept REDUCED and produce REDUCED via one carry
  pass. Signed limbs make subtraction bias-free: carries are floor
  divisions, so negative limbs borrow naturally.
- Carry extraction is exact float math: c = floor(x * 2^-8) and
  r = x - 256*c (power-of-two scaling, floor, and subtraction of
  exactly-representable integers are all exact in IEEE f32).
- `canonical` produces the unique representative in [0, p); it runs
  in int32 (a handful of sequential ripples, off the mul-heavy path)
  and is used only for compares/parity, a few times per verify.

The top-limb fold uses 2^256 ≡ 38 (mod p): a carry c out of limb 31
re-enters as 38*c split across limbs 0 and 1 so no intermediate
exceeds the exactness bound.

`add`, `sub`, `neg`, `mul` and `sqr` come in field.py's two forms
(`as_calls`): inline, or calls of jitted functions traced once a shape.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .field import _op, as_calls  # noqa: F401  (as_calls: fe.as_calls)

P = 2**255 - 19
NLIMB = 32
BITS = 8
MASK = (1 << BITS) - 1
# 2^(8*32) = 2^256 ≡ 38 (mod p)
FOLD = 38
SIGNED = True
REDUCED_BOUND = 681  # |limb| <= 680

_INV256 = np.float32(2.0**-BITS)


def to_limbs(x: int) -> np.ndarray:
    """Python int -> (32,) float32 canonical limb vector. x < 2^256."""
    assert 0 <= x < 1 << (BITS * NLIMB)
    out = np.zeros(NLIMB, np.float32)
    for i in range(NLIMB):
        out[i] = x & MASK
        x >>= BITS
    return out


def from_limbs(limbs):
    """(K,) or (K, N) limb array -> Python int(s) — for tests/host."""
    arr = np.asarray(limbs)
    ints = np.rint(arr).astype(object)
    if arr.ndim == 1:
        return sum(int(ints[i]) << (BITS * i) for i in range(arr.shape[0]))
    return [
        sum(int(ints[i, n]) << (BITS * i) for i in range(arr.shape[0]))
        for n in range(arr.shape[1])
    ]


def splat(x: int, n: int) -> jnp.ndarray:
    """Broadcast a constant element across an N-batch."""
    return jnp.tile(jnp.asarray(to_limbs(x))[:, None], (1, n))


def limbs_from_bytes(byte_rows) -> jnp.ndarray:
    """(32, N) int32 byte rows (LE, top byte pre-masked) -> limbs.

    8-bit limbs ARE bytes: the device unpack is a dtype cast."""
    return jnp.asarray(byte_rows).astype(jnp.float32)


def _carry_split(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact (floor(x/256), x mod 256) with the remainder in [0, 256)."""
    c = jnp.floor(x * _INV256)
    return c, x - c * 256.0


def _fold_top(r: jnp.ndarray, ctop: jnp.ndarray) -> jnp.ndarray:
    """Fold a carry of weight 2^256 back in as 38*c across limbs 0/1."""
    hi, lo = _carry_split(ctop * np.float32(FOLD))
    return jnp.concatenate(
        [(r[0] + lo)[None], (r[1] + hi)[None], r[2:]], axis=0
    )


def _pass32(x: jnp.ndarray) -> jnp.ndarray:
    """One parallel carry pass over 32 limbs with top fold.

    floor-division carries, so negative limbs borrow correctly."""
    c, r = _carry_split(x)
    r = jnp.concatenate([r[:1], r[1:] + c[:-1]], axis=0)
    return _fold_top(r, c[-1])


@_op
def add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """REDUCED + REDUCED -> REDUCED."""
    return _pass32(jnp.asarray(a) + jnp.asarray(b))


@_op
def sub(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """REDUCED - REDUCED -> REDUCED (signed limbs; no bias needed)."""
    return _pass32(jnp.asarray(a) - jnp.asarray(b))


@_op
def neg(a: jnp.ndarray) -> jnp.ndarray:
    return _pass32(-jnp.asarray(a))


@_op
def mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Field multiply. Inputs REDUCED (|limb| <= 680); output REDUCED.

    Schoolbook over 32 limbs: |column| <= 32 * 680^2 = 14.8M < 2^24,
    so every f32 product and partial sum is exact. One carry pass to
    8-bit limbs, split fold of the top 32 limbs by 2^256 ≡ 38, then
    two parallel passes. Bound chain in the module docstring; tests
    drive all-max limb patterns through it.
    """
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    terms = [
        jnp.pad(a[i] * b, ((i, NLIMB - 1 - i), (0, 0)))
        for i in range(NLIMB)
    ]
    return _reduce63(_balanced_sum(terms))


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

    Columns c[i+j] = sum 2*a_i*a_j (i<j) + a_i^2; worst column is
    16 doubled pairs (+ one square term on even columns):
    <= 16 * 2 * 680^2 + 680^2 = 15.3M < 2^24 — exact.
    """
    a = jnp.asarray(a)
    n = a.shape[-1]
    a2 = a + a
    diag = a * a  # (32, N)
    diag63 = jnp.stack([diag, jnp.zeros_like(diag)], axis=1).reshape(
        2 * NLIMB, n
    )[: 2 * NLIMB - 1]
    terms = [diag63]
    for i in range(NLIMB - 1):
        prod = a2[i] * a[i + 1:]  # (31-i, N), columns 2i+1 .. i+31
        terms.append(jnp.pad(prod, ((2 * i + 1, NLIMB - 1 - i), (0, 0))))
    return _reduce63(_balanced_sum(terms))


def _reduce63(c: jnp.ndarray) -> jnp.ndarray:
    """(63, N) schoolbook columns (|col| < 2^24) -> REDUCED (32, N)."""
    # Pass 1: carry into 64 limbs; |carries| <= 14.8M / 256 ≈ 5.8e4.
    cc, r = _carry_split(c)
    r = jnp.concatenate([r[:1], r[1:] + cc[:-1], cc[-1:]], axis=0)  # (64, N)
    # Fold: limb (32+m) has weight 2^256 * 2^(8m) ≡ 38 * 2^(8m).
    # |t| <= 38 * 5.9e4 ≈ 2.2M — exact; split so nothing re-overflows.
    # The m=31 hi spill (weight 2^256 again) folds once more — it is
    # small (<= ~8.7e3 * 38) by then.
    t = r[NLIMB:] * np.float32(FOLD)  # (32, N)
    hi, lo = _carry_split(t)
    hi2, lo2 = _carry_split(hi[-1] * np.float32(FOLD))
    d0 = r[0] + lo[0] + lo2
    d1 = r[1] + lo[1] + hi[0] + hi2
    rest = r[2:NLIMB] + lo[2:] + hi[1:-1]
    d = jnp.concatenate([d0[None], d1[None], rest], axis=0)
    # One pass provably lands within REDUCED (max |limb| <= 510); the
    # second is defense-in-depth margin (cheap next to the 1024
    # products above).
    d = _pass32(d)
    d = _pass32(d)
    return d


def _ripple32_int(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact sequential carry in int32: limbs in [0, 256) + signed
    out-carry. Arithmetic shift floors, so borrows propagate."""

    def step(carry, limb):
        v = limb + carry
        return v >> BITS, v & MASK

    out_c, limbs = jax.lax.scan(
        step, jnp.zeros(x.shape[-1], jnp.int32), x)
    return limbs, out_c


def canonical(x: jnp.ndarray) -> jnp.ndarray:
    """Unique representative in [0, p) with 8-bit limbs. Off hot path.

    Runs in int32 (|limbs| <= REDUCED bound fit trivially). Carry-fold
    iterations: the first ripple's out-carry is in [-3, 3] (REDUCED
    input value is within ±2.7 * 2^256); each fold re-enters 38c at
    limb 0 and re-ripples. After a borrow ripple limb 0 is >= 218, so
    the third fold's carry is provably 0 (see round-4 notes); then
    reduce 256 -> 255 bits and one conditional subtract.
    """
    xi = jnp.asarray(x).astype(jnp.int32)
    l, c = _ripple32_int(xi)
    for _ in range(3):
        l = jnp.concatenate([(l[0] + FOLD * c)[None], l[1:]], axis=0)
        l, c = _ripple32_int(l)
    # Reduce 256 -> 255 bits: bit 255 re-enters as *19.
    hb = l[31] >> 7
    l = jnp.concatenate(
        [(l[0] + 19 * hb)[None], l[1:31], (l[31] & 0x7F)[None]], axis=0)
    l, _ = _ripple32_int(l)  # value < p + 38
    # Conditional subtract: value >= p  iff  value + 19 >= 2^255.
    t = jnp.concatenate([(l[0] + 19)[None], l[1:]], axis=0)
    t, _ = _ripple32_int(t)
    ge = (t[31] >> 7) > 0
    sub_p = jnp.concatenate([t[:31], (t[31] & 0x7F)[None]], axis=0)
    return jnp.where(ge, sub_p, l).astype(jnp.float32)


def eq(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Per-lane equality mod p -> (N,) bool."""
    return is_zero(sub(a, b))


def is_zero(a: jnp.ndarray) -> jnp.ndarray:
    return jnp.all(canonical(a) == 0, axis=0)


def parity(a: jnp.ndarray) -> jnp.ndarray:
    """Low bit of the canonical representative -> (N,) int32 in {0,1}."""
    return canonical(a)[0].astype(jnp.int32) & 1


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
