"""Operations and bytes the sr25519 verify kernel needs, from its
shapes (`ops.py`'s sibling; that file is as it was).

The kernel (`crypto/tpu/sr_verify.py`, `sr25519_kernel`) verifies one
schnorrkel signature a lane, with no tables of its keys:

    ristretto_equal([s]B + [k](-A), decode(R)),  k from the host's Merlin

  decode      two ristretto255 decodes (A and R), each one
              SQRT_RATIO_M1: the 2^252-3 power (11 multiplies, 251
              squarings) inside 19 multiplies and 254 squarings, and 10
              more multiplies and 3 squarings around it
  table       the 16-entry window table of -A: 14 unified adds
  msm         64 windows of four doublings (4 multiplies and 4
              squarings each), one unified `add` of the selected table
              entry (9 multiplies) and one `add_z1` of the shared comb
              row (8 multiplies); one last add
  compare     the ristretto equality: 4 multiplies

Field elements are 22 limbs of 12 bits in int32: a multiply is 484
limb products, a squaring 253; a limb product is a multiply and an add,
two integer operations, counted against the chip's int8 peak (it has no
published int32 peak), as `ops.py` counts. LEFT OUT, so the count is a
floor on the work: the carry passes, the additions and subtractions,
the masked sums that select a lane's table entry (16 x 4 x 22 selects a
window), the 16 x 3 x 22 multiply-adds a window that select the shared
comb row, and the host's Merlin (it is not the kernel's).

Bytes, as the kernel's arrays hold them: a lane reads its key (32) and
the signature's R (32), the 64 nibbles of k and of s as int32 (2 x 256)
and three one-byte flags, and writes a one-byte verdict; the 64 shared
comb rows (16 entries x 3 coordinates x 22 limbs x 4 bytes each) are
read once a launch.

Counted for the lanes that carry a signature, not the padded bucket.
"""

from __future__ import annotations

from benchmark.ops import MUL_PRODUCTS, NLIMB, SQR_PRODUCTS, peaks

WINDOWS = 64
TABLE_ENTRIES = 16
ADD_MULS = 9            # edwards.add
ADD_Z1_MULS = 8         # edwards.add_z1
DOUBLE = (4, 4)         # edwards.double: (multiplies, squarings)
POW_2_252_M3 = (11, 251)
# ristretto.sqrt_ratio_m1 around the power, and ristretto.decode
# around that
SQRT_RATIO = (POW_2_252_M3[0] + 8, POW_2_252_M3[1] + 3)
DECODE = (SQRT_RATIO[0] + 10, SQRT_RATIO[1] + 3)
EQUAL_MULS = 4


def lane_field_ops() -> tuple[int, int]:
    """(field multiplies, field squarings) of one lane."""
    muls = 2 * DECODE[0] + (TABLE_ENTRIES - 2) * ADD_MULS \
        + WINDOWS * (4 * DOUBLE[0] + ADD_MULS + ADD_Z1_MULS) \
        + ADD_MULS + EQUAL_MULS
    sqrs = 2 * DECODE[1] + WINDOWS * 4 * DOUBLE[1]
    return muls, sqrs


def lane_ops() -> int:
    """Integer operations (2 per limb product) of one lane."""
    muls, sqrs = lane_field_ops()
    return 2 * (muls * MUL_PRODUCTS + sqrs * SQR_PRODUCTS)


def lane_bytes() -> int:
    return 32 + 32 + 2 * WINDOWS * 4 + 3 + 1


def launch_bytes() -> int:
    """The shared comb rows, once a launch."""
    return WINDOWS * TABLE_ENTRIES * 3 * NLIMB * 4


def roofline(device_kind: str, lanes: float) -> dict:
    """The least seconds the chip could take for one launch of `lanes`
    lanes, and which of its two bounds sets it."""
    pk = peaks(device_kind)
    t_ops = lanes * lane_ops() / pk["int8_ops_per_s"]
    t_mem = (lanes * lane_bytes() + launch_bytes()) / pk["hbm_bytes_per_s"]
    return {"least_s": max(t_ops, t_mem),
            "bound": "hbm_bandwidth" if t_mem >= t_ops else "int8_peak",
            "ops_s": t_ops, "bytes_s": t_mem}
