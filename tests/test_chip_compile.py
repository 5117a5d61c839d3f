"""The main path's kernels, compiled for the chip at their real shapes.

The TPU's compiler is installed here and compiles for a chip that is
described, not attached (`jax.experimental.topologies`). These five
compiles catch, at no chip time, what CPU tests cannot: a program the
v5e's compiler refuses, or one that no longer fits a 16 GB chip. A
compile that passes is not a chip run — `chip_smoke.py` is.

Why the fixtures look the way they do (docs/TESTING.md): describing
the topology loads libtpu, which only one process may hold. So the
call is made inside a module-scoped fixture, after a test of THIS file
has started in whichever xdist worker was given the file — never at
import, in a `skipif`, in `parametrize` or in conftest.py, where every
worker would race for the library and collect different tests.
Everything built from the topology (sharding, shapes) is built in
fixtures or tests too, all five tests live in this one file, and the
persistent compile cache is off around them (an entry written for a
described chip cannot be read back without one, and warns).
"""

import hashlib

import numpy as np
import pytest

LANES = 10_240   # one CommitSig per validator, padded to 1,024s
KEYS = 10_000    # the reference's MaxVotesCount
HBM_BYTES = 16 * 1000**3  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(a, sharding, rows=None):
    """ShapeDtypeStruct of array `a` on the described chip, its
    leading (lane/key) dimension optionally rescaled to `rows`."""
    import jax

    a = np.asarray(a)
    shape = a.shape if rows is None else (rows,) + a.shape[1:]
    return jax.ShapeDtypeStruct(
        shape, jax.dtypes.canonicalize_dtype(a.dtype), sharding=sharding)


def _fits(compiled, what: str):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes + m.generated_code_size_in_bytes)
    assert total < HBM_BYTES, (
        f"{what}: {total / 1e9:.2f} GB of arguments, outputs, "
        f"temporaries and code does not fit a 16 GB chip")
    return m


def _table_specs(one_chip):
    """(akeys, key_ok, atab, btab) as ExpandedKeys holds them for
    KEYS keys."""
    from tendermint_tpu.crypto.tpu import expanded as ex
    from tendermint_tpu.crypto.tpu import verify as tv

    btab = tv.b_comb_tables()
    return dict(
        akeys=_spec(np.zeros((1, 32), np.uint8), one_chip, KEYS),
        key_ok=_spec(np.zeros(1, bool), one_chip, KEYS),
        atab=_spec(np.zeros((1, ex._ROW), btab.dtype), one_chip,
                   KEYS * ex._WINDOWS * ex._ENTRIES),
        btab=_spec(btab, one_chip))


def _keys_stub(n: int):
    """An ExpandedKeys with pubkeys but no tables: the host packers
    (_prepare, _prepare_structured) need nothing else."""
    from tendermint_tpu.crypto.tpu import expanded as ex

    keys = object.__new__(ex.ExpandedKeys)
    keys.pubkeys = tuple(hashlib.sha256(b"k%d" % i).digest()
                         for i in range(n))
    keys.sharded, keys.mesh = False, None
    return keys


def test_xkernel_compiles_for_v5e(one_chip, no_compile_cache):
    """expanded._xkernel, 10,240 lanes over 10,000 keys of tables,
    messages at the widest bucket a canonical vote packs to."""
    from tendermint_tpu.crypto.tpu import expanded as ex

    n = 128
    # ~190-byte sign bytes: 3 SHA-512 blocks, bucketed to 4
    idx, packed, *_ = _keys_stub(n)._prepare(
        list(range(n)), [b"m" * 190] * n, [bytes(64)] * n)
    assert packed["msg"].shape[1] == 4 * 128 - 64
    args = {k: _spec(v, one_chip, LANES) for k, v in packed.items()}
    compiled = ex._xkernel().lower(
        idx=_spec(idx, one_chip, LANES), **args,
        **_table_specs(one_chip)).compile()
    m = _fits(compiled, "_xkernel")
    # the tables are the argument: 10,000 keys x 317,952 B
    assert m.argument_size_in_bytes > KEYS * ex._KEY_BYTES


@pytest.mark.parametrize("in_order", [False, True])
def test_skernel_compiles_for_v5e(one_chip, no_compile_cache, in_order,
                                  monkeypatch):
    """expanded._skernel (sign bytes assembled on device), the shapes
    ValidatorSet.verify_commit launches for a 10,000-validator commit:
    the lanes in the set's order (the table rows read as they lie, by
    the Pallas kernel as the chip's compiler takes it, not
    interpreted) and any other lanes (the row gather)."""
    from tendermint_tpu.crypto.tpu import expanded as ex

    monkeypatch.setattr(ex, "_interpret_pallas", lambda: False)
    from tendermint_tpu.types.block import (
        BlockID, BlockIDFlag, Commit, CommitSig, PartSetHeader)
    from tendermint_tpu.types.sign_batch import CommitSignBatch

    n = 128
    commit = Commit(
        height=123456, round=0,
        block_id=BlockID(hash=b"\xab" * 32,
                         part_set_header=PartSetHeader(4, b"\xcd" * 32)),
        signatures=[CommitSig(BlockIDFlag.COMMIT, bytes(20),
                              1_753_928_000_000_000_000 + i, bytes(64))
                    for i in range(n)])
    lanes = list(range(n))
    idx, fields, _, width, slots = _keys_stub(n)._prepare_structured(
        lanes, CommitSignBatch("chip-smoke", commit, lanes),
        [bytes(64)] * n)
    assert slots is not None        # 128 lanes in order
    args = {k: _spec(v, one_chip,
                     None if k in ex.ExpandedKeys._S_REPL else LANES)
            for k, v in fields.items()}
    compiled = ex._skernel().lower(
        idx=_spec(idx, one_chip, LANES), width=width, in_order=in_order,
        **args, **_table_specs(one_chip)).compile()
    m = _fits(compiled, "_skernel")
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == in_order
    if in_order:
        # no (69 x lanes, 128) buffer of gathered rows is left behind,
        # and a trace's reader finds the kernel under its phase
        assert m.temp_size_in_bytes < 0.7e9
        assert ex.tv.PHASE_GATHER in {
            phase for name, phase in
            ex.tv.phase_of_instructions(text).items()
            if name.startswith("comb_rows_in_order")}
    # lanes stay the minor dimension of the program's arrays: a kernel
    # operand handed in transposed turned 31,656 of them lanes-major
    # through layout assignment, and every phase 2-4x slower (PR 43)
    assert text.count(f"{LANES}]{{0,1:") < 100


def test_table_builder_compiles_for_v5e(one_chip, no_compile_cache):
    """expanded._builder at BUILD_CHUNK keys: its temporaries bound
    the table build's HBM peak (table + one chunk + these)."""
    from tendermint_tpu.crypto.tpu import expanded as ex

    chunk = ex.ExpandedKeys.BUILD_CHUNK
    compiled = ex._builder().lower(
        _spec(np.zeros((1, 32), np.uint8), one_chip, chunk)).compile()
    m = _fits(compiled, "_builder")
    assert m.output_size_in_bytes >= chunk * ex._KEY_BYTES
    # a 10,000-key build: the resident table, one chunk's rows in
    # flight, and the builder's temporaries, with room for the verify
    # kernel's own ~0.8 GB
    peak = (KEYS * ex._KEY_BYTES + m.output_size_in_bytes
            + m.temp_size_in_bytes)
    assert peak < 0.5 * HBM_BYTES, f"10k-key build peaks at {peak / 1e9} GB"


def test_sr25519_kernel_compiles_for_v5e(one_chip, no_compile_cache):
    """sr_verify._kernel at 4,096 lanes: the launch of a 16-commit
    window's sr25519 lanes on a 1,000-validator set a third of whose
    keys are sr25519 (BASELINE.json configs[3] at 1,000 validators)."""
    from helpers import sr_kernel_args

    from tendermint_tpu.crypto.tpu import sr_verify

    compiled = sr_verify._kernel().lower(**{
        k: _spec(v, one_chip)
        for k, v in sr_kernel_args(4096).items()}).compile()
    _fits(compiled, "sr25519 kernel")
