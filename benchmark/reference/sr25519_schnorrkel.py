"""Plain reference: sr25519 (schnorrkel) verification, one signature at
a time, in pure Python. It imports nothing of the program: Keccak,
STROBE, Merlin and ristretto255 are written out here from their
specifications, the curve arithmetic is the benchmark's own
`ed25519_zip215`.

What the reference node runs (Tendermint Core v0.34
crypto/sr25519/pubkey.go:34-61, through ChainSafe/go-schnorrkel, which
mirrors the Rust `schnorrkel`):

  signature   R (32 bytes, a ristretto255 encoding) || s (32 bytes,
              little-endian), with bit 7 of byte 63 SET on the wire
              (schnorrkel's marker) and cleared before use; s < L.
  public key  a ristretto255 encoding (RFC 9496).
  transcript  Merlin("SigningContext"); append("", ctx) with the EMPTY
              context pubkey.go passes; append("sign-bytes", msg);
              append("proto-name", "Schnorr-sig"); append("sign:pk", A);
              append("sign:R", R); k = challenge("sign:c", 64) mod L.
  accept      iff encode([s]B - [k]A) == R, the ristretto ENCODINGS
              compared.

Merlin is STROBE-128 over Keccak-f[1600] (merlin.cool; STROBE v1.0.2,
strobe.sourceforge.io): rate R = 166, operations AD (flags A),
meta-AD (A|M), PRF (I|A|C) and KEY (A|C), each begun with its flag byte
absorbed in a two-byte header. `benchmark/tests/test_reference_mixed.py`
pins this file on Merlin's published test vector, on RFC 9496's small
multiples of the base point and on a public key of a well-known
schnorrkel secret.

`sign` exists for tests and for the traffic generator: it never decides
`correct`. Not constant-time; public data only.
"""

from __future__ import annotations

import hashlib

from benchmark.reference import ed25519_zip215 as ed

P, L, D, SQRT_M1 = ed.P, ed.L, ed.D, ed.SQRT_M1

# ---------------------------------------------------------- Keccak-f[1600]

_RC = (
    0x0000000000000001, 0x0000000000008082, 0x800000000000808A,
    0x8000000080008000, 0x000000000000808B, 0x0000000080000001,
    0x8000000080008081, 0x8000000000008009, 0x000000000000008A,
    0x0000000000000088, 0x0000000080008009, 0x000000008000000A,
    0x000000008000808B, 0x800000000000008B, 0x8000000000008089,
    0x8000000000008003, 0x8000000000008002, 0x8000000000000080,
    0x000000000000800A, 0x800000008000000A, 0x8000000080008081,
    0x8000000000008080, 0x0000000080000001, 0x8000000080008008,
)
# rotation offsets r[x][y] (FIPS 202, table 2)
_ROT = ((0, 36, 3, 41, 18), (1, 44, 10, 45, 2), (62, 6, 43, 15, 61),
        (28, 55, 25, 21, 56), (27, 20, 39, 8, 14))
_M64 = (1 << 64) - 1


def _rol(v: int, n: int) -> int:
    return ((v << n) | (v >> (64 - n))) & _M64 if n else v


def keccak_f1600(a: list[int]) -> list[int]:
    """The 24-round permutation on 25 lanes, lane (x, y) at a[x + 5y]."""
    a = list(a)
    for rc in _RC:
        c = [a[x] ^ a[x + 5] ^ a[x + 10] ^ a[x + 15] ^ a[x + 20]
             for x in range(5)]
        d = [c[(x - 1) % 5] ^ _rol(c[(x + 1) % 5], 1) for x in range(5)]
        a = [a[i] ^ d[i % 5] for i in range(25)]
        b = [0] * 25
        for x in range(5):
            for y in range(5):
                b[y + 5 * ((2 * x + 3 * y) % 5)] = _rol(a[x + 5 * y],
                                                        _ROT[x][y])
        a = [b[x + 5 * y] ^ (~b[(x + 1) % 5 + 5 * y] & _M64
                             & b[(x + 2) % 5 + 5 * y])
             for y in range(5) for x in range(5)]
        a[0] ^= rc
    return a


def _permute(state: bytearray) -> None:
    lanes = [int.from_bytes(state[8 * i:8 * i + 8], "little")
             for i in range(25)]
    state[:] = b"".join(v.to_bytes(8, "little")
                        for v in keccak_f1600(lanes))


# ---------------------------------------------------------- STROBE-128

_R = 166
_I, _A, _C, _T, _M, _K = 1, 2, 4, 8, 16, 32


class Strobe128:
    def __init__(self, protocol: bytes):
        st = bytearray(200)
        st[0:6] = bytes([1, _R + 2, 1, 0, 1, 96])
        st[6:18] = b"STROBEv1.0.2"
        _permute(st)
        self.st, self.pos, self.pos_begin, self.flags = st, 0, 0, 0
        self.meta_ad(protocol, False)

    def _run_f(self) -> None:
        self.st[self.pos] ^= self.pos_begin
        self.st[self.pos + 1] ^= 0x04
        self.st[_R + 1] ^= 0x80
        _permute(self.st)
        self.pos = self.pos_begin = 0

    def _absorb(self, data: bytes) -> None:
        for b in data:
            self.st[self.pos] ^= b
            self.pos += 1
            if self.pos == _R:
                self._run_f()

    def _squeeze(self, n: int) -> bytes:
        out = bytearray()
        for _ in range(n):
            out.append(self.st[self.pos])
            self.st[self.pos] = 0
            self.pos += 1
            if self.pos == _R:
                self._run_f()
        return bytes(out)

    def _begin(self, flags: int, more: bool) -> None:
        if more:
            assert flags == self.flags
            return
        assert not flags & _T
        old, self.pos_begin = self.pos_begin, self.pos + 1
        self.flags = flags
        self._absorb(bytes([old, flags]))
        if flags & (_C | _K) and self.pos:
            self._run_f()

    def meta_ad(self, data: bytes, more: bool) -> None:
        self._begin(_M | _A, more)
        self._absorb(data)

    def ad(self, data: bytes, more: bool) -> None:
        self._begin(_A, more)
        self._absorb(data)

    def prf(self, n: int) -> bytes:
        self._begin(_I | _A | _C, False)
        return self._squeeze(n)


# -------------------------------------------------------------- Merlin

class Transcript:
    def __init__(self, label: bytes):
        self.strobe = Strobe128(b"Merlin v1.0")
        self.append(b"dom-sep", label)

    def append(self, label: bytes, message: bytes) -> None:
        self.strobe.meta_ad(label, False)
        self.strobe.meta_ad(len(message).to_bytes(4, "little"), True)
        self.strobe.ad(message, False)

    def challenge(self, label: bytes, n: int) -> bytes:
        self.strobe.meta_ad(label, False)
        self.strobe.meta_ad(n.to_bytes(4, "little"), True)
        return self.strobe.prf(n)


def challenge_scalar(public_key: bytes, msg: bytes, r_bytes: bytes,
                     ctx: bytes = b"") -> int:
    t = Transcript(b"SigningContext")
    t.append(b"", ctx)
    t.append(b"sign-bytes", msg)
    t.append(b"proto-name", b"Schnorr-sig")
    t.append(b"sign:pk", public_key)
    t.append(b"sign:R", r_bytes)
    return int.from_bytes(t.challenge(b"sign:c", 64), "little") % L


# ------------------------------------------------ ristretto255 (RFC 9496)

def _neg(x: int) -> bool:
    return x & 1 == 1


def _abs(x: int) -> int:
    return P - x if _neg(x) else x


def _sqrt_ratio_m1(u: int, v: int) -> tuple[bool, int]:
    """RFC 9496 4.2: (was_square, sqrt(u/v) or sqrt(i*u/v))."""
    r = (u * pow(v, 3, P) * pow(u * pow(v, 7, P) % P, (P - 5) // 8, P)) % P
    check = v * r * r % P
    correct = check == u % P
    flipped = check == -u % P
    flipped_i = check == -u * SQRT_M1 % P
    if flipped or flipped_i:
        r = r * SQRT_M1 % P
    return correct or flipped, _abs(r)


_, _INVSQRT_A_MINUS_D = _sqrt_ratio_m1(1, (-1 - D) % P)


def decode(b: bytes):
    """RFC 9496 4.3.1: an extended point, or None where the encoding
    is not canonical or names no element."""
    if len(b) != 32:
        return None
    s = int.from_bytes(b, "little")
    if s >= P or _neg(s):
        return None
    ss = s * s % P
    u1, u2 = (1 - ss) % P, (1 + ss) % P
    v = (-D * u1 * u1 - u2 * u2) % P
    was_square, invsqrt = _sqrt_ratio_m1(1, v * u2 * u2 % P)
    den_x, den_y = invsqrt * u2 % P, invsqrt * invsqrt * u2 * v % P
    x = _abs(2 * s * den_x % P)
    y = u1 * den_y % P
    t = x * y % P
    if not was_square or _neg(t) or y == 0:
        return None
    return (x, y, 1, t)


def encode(pt) -> bytes:
    """RFC 9496 4.3.2."""
    x0, y0, z0, t0 = pt
    u1 = (z0 + y0) * (z0 - y0) % P
    u2 = x0 * y0 % P
    _, invsqrt = _sqrt_ratio_m1(1, u1 * u2 * u2 % P)
    den1, den2 = invsqrt * u1 % P, invsqrt * u2 % P
    z_inv = den1 * den2 * t0 % P
    if _neg(t0 * z_inv % P):
        x, y, den_inv = y0 * SQRT_M1 % P, x0 * SQRT_M1 % P, \
            den1 * _INVSQRT_A_MINUS_D % P
    else:
        x, y, den_inv = x0, y0, den2
    if _neg(x * z_inv % P):
        y = -y % P
    return _abs(den_inv * (z0 - y) % P).to_bytes(32, "little")


# ---------------------------------------------------------- schnorrkel

def verify(public_key: bytes, msg: bytes, sig: bytes,
           ctx: bytes = b"") -> bool:
    if len(public_key) != 32 or len(sig) != 64:
        return False
    if not sig[63] & 0x80:
        return False           # not marked as a schnorrkel signature
    a_pt = decode(public_key)
    if a_pt is None:
        return False
    r_bytes = sig[:32]
    s = int.from_bytes(sig[32:], "little") & ((1 << 255) - 1)
    if s >= L:
        return False
    k = challenge_scalar(public_key, msg, r_bytes, ctx)
    v = ed.pt_add(ed.scalar_mult(s, ed._B_PT),
                  ed.scalar_mult(k, ed.pt_neg(a_pt)))
    return encode(v) == r_bytes


def expand_mini(mini: bytes) -> tuple[int, bytes]:
    """schnorrkel's MiniSecretKey -> (scalar, nonce), ExpandEd25519:
    the clamped half of SHA-512 divided by the cofactor."""
    h = hashlib.sha512(mini).digest()
    key = bytearray(h[:32])
    key[0] &= 248
    key[31] &= 63
    key[31] |= 64
    return int.from_bytes(bytes(key), "little") >> 3, h[32:]


def public_key(mini: bytes) -> bytes:
    return encode(ed.scalar_mult(expand_mini(mini)[0], ed._B_PT))


def sign(mini: bytes, msg: bytes, ctx: bytes = b"") -> bytes:
    """A deterministic-nonce signature any schnorrkel verifier takes
    (schnorrkel draws its nonce at random; the verifier cannot tell)."""
    key, nonce = expand_mini(mini)
    pub = encode(ed.scalar_mult(key, ed._B_PT))
    r = int.from_bytes(hashlib.sha512(nonce + pub + msg).digest(),
                       "little") % L
    r_bytes = encode(ed.scalar_mult(r, ed._B_PT))
    s = (challenge_scalar(pub, msg, r_bytes, ctx) * key + r) % L
    sig = bytearray(r_bytes + s.to_bytes(32, "little"))
    sig[63] |= 0x80
    return bytes(sig)
