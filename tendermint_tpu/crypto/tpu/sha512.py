"""Batched SHA-512 on TPU, in uint32 (hi, lo) pairs.

The ed25519 challenge scalar k = SHA-512(R || A || M) is the only
variable-length-message hash on the verify hot path (reference:
crypto/ed25519/ed25519.go:149-156 via ed25519consensus). Hashing 10k+
messages one at a time in host Python costs tens of milliseconds — far
over the latency budget — and this host has a single CPU core, so the
hash moves onto the device with everything else: lanes are SIMD over
the batch, and each 64-bit word is an (hi, lo) uint32 pair since the
TPU VPU is a 32-bit machine.

Host-side responsibility (see `pad_messages`): append standard SHA-512
padding (0x80, zeros, 128-bit big-endian bit length) and report each
lane's block count. The device runs every lane through max_blocks
compression rounds and freezes a lane's state once its own block count
is reached — constant shapes, no data-dependent control flow.
"""

from __future__ import annotations

import functools

import numpy as np

_IV = [
    0x6A09E667F3BCC908, 0xBB67AE8584CAA73B, 0x3C6EF372FE94F82B,
    0xA54FF53A5F1D36F1, 0x510E527FADE682D1, 0x9B05688C2B3E6C1F,
    0x1F83D9ABFB41BD6B, 0x5BE0CD19137E2179,
]

_K = [
    0x428A2F98D728AE22, 0x7137449123EF65CD, 0xB5C0FBCFEC4D3B2F, 0xE9B5DBA58189DBBC,
    0x3956C25BF348B538, 0x59F111F1B605D019, 0x923F82A4AF194F9B, 0xAB1C5ED5DA6D8118,
    0xD807AA98A3030242, 0x12835B0145706FBE, 0x243185BE4EE4B28C, 0x550C7DC3D5FFB4E2,
    0x72BE5D74F27B896F, 0x80DEB1FE3B1696B1, 0x9BDC06A725C71235, 0xC19BF174CF692694,
    0xE49B69C19EF14AD2, 0xEFBE4786384F25E3, 0x0FC19DC68B8CD5B5, 0x240CA1CC77AC9C65,
    0x2DE92C6F592B0275, 0x4A7484AA6EA6E483, 0x5CB0A9DCBD41FBD4, 0x76F988DA831153B5,
    0x983E5152EE66DFAB, 0xA831C66D2DB43210, 0xB00327C898FB213F, 0xBF597FC7BEEF0EE4,
    0xC6E00BF33DA88FC2, 0xD5A79147930AA725, 0x06CA6351E003826F, 0x142929670A0E6E70,
    0x27B70A8546D22FFC, 0x2E1B21385C26C926, 0x4D2C6DFC5AC42AED, 0x53380D139D95B3DF,
    0x650A73548BAF63DE, 0x766A0ABB3C77B2A8, 0x81C2C92E47EDAEE6, 0x92722C851482353B,
    0xA2BFE8A14CF10364, 0xA81A664BBC423001, 0xC24B8B70D0F89791, 0xC76C51A30654BE30,
    0xD192E819D6EF5218, 0xD69906245565A910, 0xF40E35855771202A, 0x106AA07032BBD1B8,
    0x19A4C116B8D2D0C8, 0x1E376C085141AB53, 0x2748774CDF8EEB99, 0x34B0BCB5E19B48A8,
    0x391C0CB3C5C95A63, 0x4ED8AA4AE3418ACB, 0x5B9CCA4F7763E373, 0x682E6FF3D6B2B8A3,
    0x748F82EE5DEFB2FC, 0x78A5636F43172F60, 0x84C87814A1F0AB72, 0x8CC702081A6439EC,
    0x90BEFFFA23631E28, 0xA4506CEBDE82BDE9, 0xBEF9A3F7B2C67915, 0xC67178F2E372532B,
    0xCA273ECEEA26619C, 0xD186B8C721C0C207, 0xEADA7DD6CDE0EB1E, 0xF57D4F7FEE6ED178,
    0x06F067AA72176FBA, 0x0A637DC5A2C898A6, 0x113F9804BEF90DAE, 0x1B710B35131C471B,
    0x28DB77F523047D84, 0x32CAAB7B40C72493, 0x3C9EBE0A15C9BEBC, 0x431D67C49C100D4C,
    0x4CC5D4BECB3E42B6, 0x597F299CFC657E2A, 0x5FCB6FAB3AD6FAEC, 0x6C44198C4A475817,
]


def _split64(vals) -> np.ndarray:
    """list of uint64 ints -> (len, 2) uint32 (hi, lo)."""
    a = np.asarray(vals, np.uint64)
    return np.stack([(a >> np.uint64(32)).astype(np.uint32),
                     (a & np.uint64(0xFFFFFFFF)).astype(np.uint32)], axis=-1)


def pad_messages(msgs: list[bytes], prefix_len: int = 0) -> tuple[np.ndarray, np.ndarray]:
    """SHA-512-pad variable-length messages into a (N, B*128 - prefix_len)
    uint8 buffer, assuming `prefix_len` fixed bytes (e.g. R||A = 64) will
    be prepended on device. Returns (padded, nblocks).

    Fully vectorized: one np.repeat + one fancy-index scatter; no
    per-message Python beyond the b"".join.
    """
    n = len(msgs)
    lens = np.fromiter(map(len, msgs), np.int64, count=n)
    total_lens = lens + prefix_len
    # blocks: content + 1 (0x80) + 16 (length) rounded up to 128
    nblocks = (total_lens + 1 + 16 + 127) // 128
    max_blocks = int(nblocks.max()) if n else 1
    width = max_blocks * 128 - prefix_len
    if n >= 256:
        # Native fast path (tendermint_tpu/native/pack.c): one C pass
        # replaces the numpy scatter/group fill AND the tail writes —
        # host packing serializes ahead of the launch, so this sits
        # directly on the commit-latency budget.
        from ...native import lib as _native_lib

        L = _native_lib()
        if L is not None:
            flat = np.frombuffer(b"".join(msgs), np.uint8)
            starts = np.zeros(n, np.int64)
            np.cumsum(lens[:-1], out=starts[1:])
            out = np.zeros((n, width), np.uint8)
            nb = np.empty(n, np.int64)
            L.tm_pack_pad(flat, starts, np.ascontiguousarray(lens),
                          n, width, prefix_len, out, nb)
            return out, nb.astype(np.int32)  # same dtype as the
            # numpy path below (compress_blocks' (N,) int32 contract)
    out = np.zeros((n, width), np.uint8)
    uniq = np.unique(lens) if n else lens
    if n and uniq.size <= 8:
        # Fast path: few distinct lengths (a commit's vote sign-bytes
        # differ only in varint-timestamp width, 2-3 values) — one bulk
        # reshape+copy per length group instead of the per-byte scatter
        # (8 ms -> ~1 ms at 10,240 lanes; the scatter was the single
        # largest host cost in the verify hot path).
        for length in uniq.tolist():
            if not length:
                continue
            mask = lens == length
            ii = np.nonzero(mask)[0]
            block = np.frombuffer(
                b"".join(msgs[i] for i in ii), np.uint8
            ).reshape(ii.size, length)
            out[mask, :length] = block
    else:
        flat = np.frombuffer(b"".join(msgs), np.uint8)
        if flat.size:
            rows = np.repeat(np.arange(n), lens)
            starts = np.concatenate([[0], np.cumsum(lens)[:-1]])
            cols = np.arange(flat.size) - np.repeat(starts, lens)
            out[rows, cols] = flat
    out[np.arange(n), lens] = 0x80
    # 128-bit big-endian bit length at the end of each lane's final block;
    # bit lengths here always fit 4 bytes (messages < 512 MiB).
    bitlen = (total_lens * 8).astype(np.uint64)
    end = nblocks * 128 - prefix_len  # exclusive end col of final block
    for i in range(4):
        out[np.arange(n), end - 1 - i] = ((bitlen >> np.uint64(8 * i)) & np.uint64(0xFF)).astype(np.uint8)
    return out, nblocks.astype(np.int32)


@functools.cache
def _consts():
    # NUMPY on purpose: caching jnp arrays is a tracer leak — an array
    # materialized during one jit trace must not be reused in another.
    # numpy constants fold into each trace safely.
    return _split64(_K), _split64(_IV)


def _jnp():
    import jax
    import jax.numpy as jnp

    return jax, jnp


def _add64(ah, al, bh, bl):
    jax, jnp = _jnp()
    lo = al + bl
    carry = (lo < al).astype(jnp.uint32)
    return ah + bh + carry, lo


def _add64m(*pairs):
    """Sum of several (hi, lo) uint64 pairs."""
    h, l = pairs[0]
    for ph, pl in pairs[1:]:
        h, l = _add64(h, l, ph, pl)
    return h, l


def _ror64(h, l, r: int):
    if r == 32:
        return l, h
    if r > 32:
        h, l, r = l, h, r - 32
    jnp32 = np.uint32(32 - r)
    r = np.uint32(r)
    return (h >> r) | (l << jnp32), (l >> r) | (h << jnp32)


def _shr64(h, l, r: int):
    r32 = np.uint32(r)
    return h >> r32, (l >> r32) | (h << np.uint32(32 - r))


def _xor3(a, b, c):
    return (a[0] ^ b[0] ^ c[0], a[1] ^ b[1] ^ c[1])


def compress_blocks(words, nblocks):
    """Run SHA-512 over per-lane padded blocks.

    words: (B, 16, 2, N) uint32 — big-endian 64-bit message words as
    (hi, lo) pairs; B = max blocks in the batch.
    nblocks: (N,) int32 — per-lane block count; lanes freeze after
    their own final block.

    Returns (8, 2, N) uint32 digest words.
    """
    jax, jnp = _jnp()
    k_const, iv = _consts()
    b_total, _, _, n = words.shape
    state = jnp.broadcast_to(iv[:, :, None], (8, 2, n)).astype(jnp.uint32)

    def one_block(state, block_words, active):
        # Working vars a..h as (2, N) pairs, unpacked from state.
        v = [(state[i, 0], state[i, 1]) for i in range(8)]

        def round_body(t, carry):
            a, b, c, d, e, f, g, h, w = carry
            wt = (w[0, 0], w[0, 1])
            kt_pair = jax.lax.dynamic_index_in_dim(k_const, t, 0, keepdims=False)
            kt = (kt_pair[0], kt_pair[1])
            s1 = _xor3(_ror64(*e, 14), _ror64(*e, 18), _ror64(*e, 41))
            ch = ((e[0] & f[0]) ^ (~e[0] & g[0]), (e[1] & f[1]) ^ (~e[1] & g[1]))
            t1 = _add64m(h, s1, ch, kt, wt)
            s0 = _xor3(_ror64(*a, 28), _ror64(*a, 34), _ror64(*a, 39))
            maj = (
                (a[0] & b[0]) ^ (a[0] & c[0]) ^ (b[0] & c[0]),
                (a[1] & b[1]) ^ (a[1] & c[1]) ^ (b[1] & c[1]),
            )
            t2 = _add64m(s0, maj)
            new_e = _add64m(d, t1)
            new_a = _add64m(t1, t2)
            # Message schedule: push W[t+16] computed from the window.
            w1 = (w[1, 0], w[1, 1])
            w9 = (w[9, 0], w[9, 1])
            w14 = (w[14, 0], w[14, 1])
            sg0 = _xor3(_ror64(*w1, 1), _ror64(*w1, 8), _shr64(*w1, 7))
            sg1 = _xor3(_ror64(*w14, 19), _ror64(*w14, 61), _shr64(*w14, 6))
            wn = _add64m(wt, sg0, w9, sg1)
            w = jnp.concatenate(
                [w[1:], jnp.stack([wn[0], wn[1]])[None]], axis=0
            )
            return (new_a, a, b, c, new_e, e, f, g, w)

        a, b, c, d, e, f, g, h, _ = jax.lax.fori_loop(
            0, 80, round_body, (*v, block_words)
        )
        out = []
        for i, pair in enumerate((a, b, c, d, e, f, g, h)):
            sh, sl = _add64(state[i, 0], state[i, 1], pair[0], pair[1])
            out.append(jnp.stack([sh, sl]))
        new_state = jnp.stack(out)
        return jnp.where(active[None, None, :], new_state, state)

    for bi in range(b_total):
        state = one_block(state, words[bi], bi < nblocks)
    return state


def bytes_to_words(msg_bytes):
    """(N, B*128) uint8/int32 device array -> (B, 16, 2, N) uint32 words."""
    jax, jnp = _jnp()
    n, width = msg_bytes.shape
    b_total = width // 128
    x = msg_bytes.astype(jnp.uint32).reshape(n, b_total, 16, 8)
    hi = (x[..., 0] << 24) | (x[..., 1] << 16) | (x[..., 2] << 8) | x[..., 3]
    lo = (x[..., 4] << 24) | (x[..., 5] << 16) | (x[..., 6] << 8) | x[..., 7]
    return jnp.stack([hi, lo], axis=3).transpose(1, 2, 3, 0)  # (B, 16, 2, N)


def rows_to_words(rows):
    """(W, N) byte rows, lanes minor, W a multiple of 8 -> (W/8, 2, N)
    uint32: the big-endian 64-bit words of each lane's W bytes as
    (hi, lo) pairs, in the order compress_blocks reads them. The lanes
    stay where they are, so nothing is transposed."""
    jax, jnp = _jnp()
    w, n = rows.shape
    x = rows.astype(jnp.uint32).reshape(w // 8, 8, n)
    hi = (x[:, 0] << 24) | (x[:, 1] << 16) | (x[:, 2] << 8) | x[:, 3]
    lo = (x[:, 4] << 24) | (x[:, 5] << 16) | (x[:, 6] << 8) | x[:, 7]
    return jnp.stack([hi, lo], axis=1)


def challenge_words(r, a, msg):
    """(B, 16, 2, N) words of R || A || M for compress_blocks. r, a:
    (N, 32) byte rows. msg is what follows them, padded: (N, W) byte
    rows (the bytes front-ends), or the (W/8, 2, N) words the
    structured assembly forms lanes-minor (expanded.assemble_core) —
    R || A is 64 bytes, eight whole words, so its words are formed
    apart and put in front."""
    jax, jnp = _jnp()
    if msg.ndim == 2:
        return bytes_to_words(jnp.concatenate([r, a, msg], axis=1))
    head = rows_to_words(jnp.concatenate([r, a], axis=1).T)
    words = jnp.concatenate([head, msg], axis=0)
    return words.reshape(words.shape[0] // 16, 16, 2, words.shape[2])


def digest_bytes_le(state):
    """(8, 2, N) uint32 digest -> (64, N) int32 bytes, little-endian order
    (byte row j = j-th byte of the digest as an integer's LE expansion)."""
    jax, jnp = _jnp()
    rows = []
    for wi in range(8):
        for part in (0, 1):  # hi covers digest bytes 8wi..+3, lo +4..+7
            word = state[wi, part]
            for shift in (24, 16, 8, 0):
                rows.append(((word >> np.uint32(shift)) & np.uint32(0xFF)).astype(jnp.int32))
    return jnp.stack(rows)
