"""Seeded keys, OpenSSL signing in a pool of processes that never import
JAX, and the planted signatures. Shared by the traffic drivers; imports
nothing of the program (OpenSSL signs, it never decides `correct`)."""

from __future__ import annotations

import concurrent.futures
import hashlib
import multiprocessing
import os

from cryptography.hazmat.primitives import serialization
from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PrivateKey)

from benchmark.reference import canonical
from benchmark.reference import ed25519_zip215 as ref


def key_seed(seed: int, purpose: str, i: int) -> bytes:
    return hashlib.sha256(f"bench/{purpose}/{seed}/{i}".encode()).digest()


def private_key(seed: int, purpose: str, i: int):
    return Ed25519PrivateKey.from_private_bytes(key_seed(seed, purpose, i))


def private_keys(seed: int, purpose: str, n: int) -> list:
    return [private_key(seed, purpose, i) for i in range(n)]


def public_bytes(priv) -> bytes:
    return priv.public_key().public_bytes(
        serialization.Encoding.Raw, serialization.PublicFormat.Raw)


def _ordered(seed: int, n: int) -> tuple[list[int], list, list[bytes]]:
    """Key numbers, private keys and public keys in validator-set order
    (equal power: by address, reference types/validator_set.go)."""
    keys = private_keys(seed, "val", n)
    pubs = [public_bytes(k) for k in keys]
    order = sorted(range(n), key=lambda i: canonical.address(pubs[i]))
    return order, [keys[i] for i in order], [pubs[i] for i in order]


def validator_order(seed: int, n: int) -> tuple[list[int], list[bytes]]:
    order, _, pubs = _ordered(seed, n)
    return order, pubs


# -------------------------------------------------------------- the pool

_KEYS: dict = {}   # a pool worker's keys, in validator-set order


def sign_slice(seed: int, n: int, lo: int, hi: int, pre: bytes,
               suf: bytes, times: list[int]) -> bytes:
    """Pool worker: the precommit signatures of validators lo..hi-1
    over the reference's sign bytes at times[i - lo] (0 = no vote: 64
    zero bytes), joined."""
    if (seed, n) not in _KEYS:
        _KEYS.clear()
        _KEYS[(seed, n)] = _ordered(seed, n)[1]
    keys = _KEYS[(seed, n)]
    return b"".join(
        keys[i].sign(canonical.with_timestamp(pre, suf, t))
        if t else b"\0" * 64
        for i, t in zip(range(lo, hi), times))


def make_pool(workers: int | None = None):
    workers = workers or max(2, min(8, (os.cpu_count() or 2) - 2))
    return concurrent.futures.ProcessPoolExecutor(
        max_workers=workers,
        mp_context=multiprocessing.get_context("spawn"))


# ------------------------------------------------- planted signatures


def corrupt(sig: bytes, kind: str) -> bytes:
    """A signature that must be refused: one bit of R, one bit of S
    (still < L), or S + L (the same point, a non-canonical scalar)."""
    b = bytearray(sig)
    if kind == "r_bit":
        b[7] ^= 0x01
    elif kind == "s_bit":
        b[40] ^= 0x01
    elif kind == "s_plus_l":
        s = int.from_bytes(sig[32:], "little") + ref.L
        b[32:] = s.to_bytes(32, "little")
    else:
        raise ValueError(kind)
    return bytes(b)


def zip215_only(key_seed_bytes: bytes, pub: bytes, msg: bytes) -> bytes:
    """A signature only ZIP-215 accepts: R is the identity encoded
    non-canonically (y = p + 1), S = k * a. RFC 8032 verifiers refuse
    the encoding; consensus must accept it."""
    a = ref._clamp(hashlib.sha512(key_seed_bytes).digest())
    r_enc = (ref.P + 1).to_bytes(32, "little")
    k = int.from_bytes(hashlib.sha512(r_enc + pub + msg).digest(),
                       "little") % ref.L
    return r_enc + ((k * a) % ref.L).to_bytes(32, "little")
