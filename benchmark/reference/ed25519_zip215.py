"""Plain reference: pure-Python ed25519 with ZIP-215 verification.

A COPY of the consensus-normative oracle (tendermint_tpu/crypto/
ed25519_ref.py at PR 22), kept with the benchmark so that no later PR
can change what `correct` is compared with. It imports nothing of the
program. ZIP-215 (https://zips.z.cash/zip-0215), the accept set of the
`ed25519consensus` verifier the reference node uses for votes:

  1. ``S`` must be canonical (``S < L``); otherwise reject.
  2. ``A`` and ``R`` may be non-canonical encodings (y taken mod p,
     sign bit 1 with x == 0 accepted); small-order points accepted.
  3. The cofactored equation is checked: [8][S]B == [8]R + [8][k]A,
     k = SHA-512(R_bytes || A_bytes || M) mod L over the ORIGINAL
     encodings.

`verify` takes two switches that exist only for the controls (the
weakened guarantees `correct` has to fail, benchmark/README.md):
`strict` is RFC 8032 as OpenSSL implements it (canonical encodings,
cofactorless equation) where ZIP-215 is required, `check_s=False`
drops the S < L check. Not constant-time; public data only.
"""


from __future__ import annotations

import hashlib

# Curve constants for edwards25519.
P = 2**255 - 19
L = 2**252 + 27742317777372353535851937790883648493
D = (-121665 * pow(121666, P - 2, P)) % P
SQRT_M1 = pow(2, (P - 1) // 4, P)  # sqrt(-1), the canonical 2^((p-1)/4)

# Base point.
_BY = (4 * pow(5, P - 2, P)) % P


def _recover_x(y: int, sign: int) -> int | None:
    """x from y per ZIP-215 decompression; None if y^2-1/(dy^2+1) is non-square."""
    u = (y * y - 1) % P
    v = (D * y * y + 1) % P
    # Candidate root of u/v: x = u v^3 (u v^7)^((p-5)/8)
    x = (u * pow(v, 3, P) * pow(u * pow(v, 7, P) % P, (P - 5) // 8, P)) % P
    vxx = (v * x * x) % P
    if vxx == u:
        pass
    elif vxx == (P - u) % P:
        x = (x * SQRT_M1) % P
    else:
        return None
    if x & 1 != sign:
        x = (P - x) % P
    # Note: if x == 0 and sign == 1, (P - 0) % P == 0 — accepted with x=0,
    # per ZIP-215 (RFC 8032 would reject this).
    return x


def decompress(b: bytes) -> tuple[int, int] | None:
    """ZIP-215 point decompression: non-canonical y accepted (reduced mod p)."""
    if len(b) != 32:
        return None
    y_raw = int.from_bytes(b, "little")
    sign = (y_raw >> 255) & 1
    y = (y_raw & ((1 << 255) - 1)) % P
    x = _recover_x(y, sign)
    if x is None:
        return None
    return (x, y)


def compress(pt: tuple[int, int]) -> bytes:
    x, y = pt
    return ((y % P) | ((x & 1) << 255)).to_bytes(32, "little")


# Extended homogeneous coordinates (X : Y : Z : T), x = X/Z, y = Y/Z, T = XY/Z.
IDENTITY = (0, 1, 1, 0)
_B_PT = None  # set below


def to_extended(pt: tuple[int, int]) -> tuple[int, int, int, int]:
    x, y = pt
    return (x, y, 1, (x * y) % P)


def from_extended(e: tuple[int, int, int, int]) -> tuple[int, int]:
    x, y, z, _ = e
    zi = pow(z, P - 2, P)
    return ((x * zi) % P, (y * zi) % P)


def pt_add(p, q):
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = ((y1 - x1) * (y2 - x2)) % P
    b = ((y1 + x1) * (y2 + x2)) % P
    c = (2 * t1 * t2 * D) % P
    dd = (2 * z1 * z2) % P
    e = b - a
    f = dd - c
    g = dd + c
    h = b + a
    return ((e * f) % P, (g * h) % P, (f * g) % P, (e * h) % P)


def pt_double(p):
    x1, y1, z1, _ = p
    a = (x1 * x1) % P
    b = (y1 * y1) % P
    c = (2 * z1 * z1) % P
    h = (a + b) % P
    e = (h - (x1 + y1) * (x1 + y1)) % P
    g = (a - b) % P
    f = (c + g) % P
    return ((e * f) % P, (g * h) % P, (f * g) % P, (e * h) % P)


def pt_neg(p):
    x, y, z, t = p
    return ((P - x) % P, y, z, (P - t) % P)


def scalar_mult(k: int, p) -> tuple[int, int, int, int]:
    acc = IDENTITY
    while k > 0:
        if k & 1:
            acc = pt_add(acc, p)
        p = pt_double(p)
        k >>= 1
    return acc


_B_PT = to_extended((_recover_x(_BY, 0), _BY))


def pt_equal(p, q) -> bool:
    x1, y1, z1, _ = p
    x2, y2, z2, _ = q
    return (x1 * z2 - x2 * z1) % P == 0 and (y1 * z2 - y2 * z1) % P == 0


def is_identity(p) -> bool:
    x, y, z, _ = p
    return x % P == 0 and (y - z) % P == 0


def _canonical(b: bytes) -> bool:
    return (int.from_bytes(b, "little") & ((1 << 255) - 1)) < P


def verify(public_key: bytes, message: bytes, signature: bytes, *,
           strict: bool = False, check_s: bool = True) -> bool:
    """ZIP-215 cofactored verification, the consensus-normative accept
    set (defaults). `strict` / `check_s=False`: the controls only."""
    if len(public_key) != 32 or len(signature) != 64:
        return False
    if strict and not (_canonical(public_key)
                       and _canonical(signature[:32])):
        return False
    a_pt = decompress(public_key)
    if a_pt is None:
        return False
    r_pt = decompress(signature[:32])
    if r_pt is None:
        return False
    s = int.from_bytes(signature[32:], "little")
    if check_s and s >= L:
        return False
    k = (
        int.from_bytes(
            hashlib.sha512(signature[:32] + public_key + message).digest(), "little"
        )
        % L
    )
    # [8]([S]B - [k]A - R) == identity
    sb = scalar_mult(s, _B_PT)
    ka = scalar_mult(k, to_extended(a_pt))
    v = pt_add(sb, pt_neg(ka))
    v = pt_add(v, pt_neg(to_extended(r_pt)))
    if not strict:
        for _ in range(3):
            v = pt_double(v)
    return is_identity(v)


# --- RFC 8032 signing (tests; the benchmark's traffic is signed by OpenSSL) ---


def _clamp(h: bytes) -> int:
    a = int.from_bytes(h[:32], "little")
    a &= (1 << 254) - 8
    a |= 1 << 254
    return a


def public_key_from_seed(seed: bytes) -> bytes:
    h = hashlib.sha512(seed).digest()
    a = _clamp(h)
    return compress(from_extended(scalar_mult(a, _B_PT)))


def sign(seed: bytes, message: bytes) -> bytes:
    h = hashlib.sha512(seed).digest()
    a = _clamp(h)
    prefix = h[32:]
    pub = compress(from_extended(scalar_mult(a, _B_PT)))
    r = int.from_bytes(hashlib.sha512(prefix + message).digest(), "little") % L
    r_enc = compress(from_extended(scalar_mult(r, _B_PT)))
    k = int.from_bytes(hashlib.sha512(r_enc + pub + message).digest(), "little") % L
    s = (r + k * a) % L
    return r_enc + s.to_bytes(32, "little")
