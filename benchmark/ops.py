"""Operations and bytes the verify kernels need, from their shapes.

The structured/expanded kernel (`crypto/tpu/expanded.py`, `_xcore`)
verifies one signature per lane against per-validator comb tables:

    [8]([S]B - [k]A - R) == 0,  k = SHA-512(R || A || M) mod L

with [k](-A) as 69 table rows added (one unified `add`, 9 field
multiplies) and [S]B as 69 fixed-base rows added (`add_z1`, 8 field
multiplies), R decompressed (a 2^252-3 power: 255 squarings and 20
multiplies), two more adds and three doublings. Field elements are 22
limbs of 12 bits in int32: a multiply is 22 x 22 = 484 limb products,
a squaring 22 + 231 = 253. A limb product is a multiply and an add,
two integer operations, counted against the chip's int8 peak (it has
no published int32 peak). SHA-512 and the carry passes are adds,
shifts and logic, left out: the count is a floor on the work.

Bytes: each lane reads its 69 selected table rows of 512 bytes from
the resident tables, its signature, key index and sign bytes, and
writes one verdict. The tables themselves are not streamed.

Counted for the lanes that carry a signature, not the padded bucket:
padding is waste, not work the algorithm needs.
"""

from __future__ import annotations

import json
import os

NLIMB = 22
MUL_PRODUCTS = NLIMB * NLIMB                       # 484
SQR_PRODUCTS = NLIMB + NLIMB * (NLIMB - 1) // 2    # 253
WINDOWS = 69
ROW_BYTES = 128 * 4

ADD_MULS = 9          # edwards.add
ADD_Z1_MULS = 8       # edwards.add_z1
DOUBLE = (4, 4)       # edwards.double: (multiplies, squarings)
DECOMPRESS = (20, 255)  # edwards.decompress with pow_2_252_m3


def expanded_lane_field_ops() -> tuple[int, int]:
    """(field multiplies, field squarings) of one lane."""
    muls = WINDOWS * (ADD_MULS + ADD_Z1_MULS) + 2 * ADD_MULS \
        + 3 * DOUBLE[0] + DECOMPRESS[0]
    sqrs = 3 * DOUBLE[1] + DECOMPRESS[1]
    return muls, sqrs


def expanded_lane_ops() -> int:
    """Integer operations (2 per limb product) of one lane."""
    muls, sqrs = expanded_lane_field_ops()
    return 2 * (muls * MUL_PRODUCTS + sqrs * SQR_PRODUCTS)


def expanded_lane_bytes(msg_bytes: int) -> int:
    """HBM bytes of one lane: gathered rows + inputs + verdict."""
    return WINDOWS * ROW_BYTES + 64 + 4 + msg_bytes + 1


def peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in peaks.json")
    return table[device_kind]


def roofline(device_kind: str, lanes: float, msg_bytes: int) -> dict:
    """The least seconds the chip could take for `lanes` lanes, and
    which of its two bounds sets it."""
    pk = peaks(device_kind)
    t_ops = lanes * expanded_lane_ops() / pk["int8_ops_per_s"]
    t_mem = lanes * expanded_lane_bytes(msg_bytes) / pk["hbm_bytes_per_s"]
    return {"least_s": max(t_ops, t_mem),
            "bound": "hbm_bandwidth" if t_mem >= t_ops else "int8_peak",
            "ops_s": t_ops, "bytes_s": t_mem}
