"""Shared test fixtures: deterministic validator networks and signed
commits (the analogue of the reference's consensus/common_test.go
harness building blocks)."""

from __future__ import annotations

import hashlib

from tendermint_tpu.crypto import ed25519
from tendermint_tpu.state import State, make_genesis_state
from tendermint_tpu.types.block import Block, BlockID, BlockIDFlag, Commit, CommitSig
from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
from tendermint_tpu.types.priv_validator import MockPV
from tendermint_tpu.types.validator_set import ValidatorSet
from tendermint_tpu.types.vote import Vote, VoteType

CHAIN_ID = "test-chain"
# A genesis slightly in the FUTURE makes BFT time run ahead of the
# wall clock, so every vote timestamp hits the deterministic
# block_time + time_iota floor (consensus voteTime) instead of the
# wall clock — medians then agree across nodes regardless of which
# precommit subset each assembles, which evidence timestamps rely on.
import time as _time  # noqa: E402

GENESIS_TIME = (_time.time_ns() // 1_000_000_000 + 3600) * 1_000_000_000


def deterministic_pv(i: int) -> MockPV:
    seed = hashlib.sha256(b"val-seed-%d" % i).digest()
    return MockPV(ed25519.Ed25519PrivKey(seed))


def make_genesis(n_vals: int = 4, power: int = 10,
                 chain_id: str = CHAIN_ID) -> tuple[GenesisDoc, list[MockPV]]:
    pvs = [deterministic_pv(i) for i in range(n_vals)]
    gdoc = GenesisDoc(
        chain_id=chain_id,
        genesis_time=GENESIS_TIME,
        validators=[
            GenesisValidator(pv.get_pub_key(), power) for pv in pvs
        ],
    )
    gdoc.validate_and_complete()
    return gdoc, pvs


def make_genesis_state_and_pvs(n_vals: int = 4) -> tuple[State, list[MockPV]]:
    gdoc, pvs = make_genesis(n_vals)
    return make_genesis_state(gdoc), pvs


def sign_commit(valset: ValidatorSet, pvs: list[MockPV], chain_id: str,
                height: int, round_: int, block_id: BlockID,
                timestamp: int) -> Commit:
    """Commit with a precommit from every validator we hold a key for;
    validators without a known key get an ABSENT slot (still +2/3 as
    long as they are a minority of the power)."""
    by_addr = {pv.get_pub_key().address(): pv for pv in pvs}
    sigs = []
    for idx, val in enumerate(valset.validators):
        pv = by_addr.get(val.address)
        if pv is None:
            sigs.append(CommitSig.absent())
            continue
        vote = Vote(
            type=VoteType.PRECOMMIT,
            height=height,
            round=round_,
            block_id=block_id,
            timestamp=timestamp,
            validator_address=val.address,
            validator_index=idx,
        )
        pv.sign_vote(chain_id, vote)
        sigs.append(CommitSig(
            BlockIDFlag.COMMIT, val.address, timestamp, vote.signature
        ))
    return Commit(height, round_, block_id, sigs)


def next_block(state: State, pvs: list[MockPV],
               last_commit: Commit | None,
               txs: list[bytes] | None = None) -> tuple[Block, BlockID]:
    """Build the next valid block for `state` (+ its BlockID)."""
    height = state.last_block_height + 1
    if state.last_block_height == 0:
        height = state.initial_height
        time_ns = state.last_block_time
    else:
        from tendermint_tpu.state import median_time

        time_ns = median_time(last_commit, state.last_validators)
    proposer = state.validators.get_proposer().address
    block = state.make_block(
        height, txs or [], last_commit, [], proposer, time_ns
    )
    return block, block.block_id()


def commit_for(state: State, pvs: list[MockPV], block: Block,
               block_id: BlockID) -> Commit:
    """Commit for `block` signed by the CURRENT validators, timestamped
    1s after the block (so the next block's median time advances)."""
    return sign_commit(
        state.validators, pvs, state.chain_id, block.header.height, 0,
        block_id, block.header.time + 1_000_000_000,
    )


def sr_kernel_args(n: int, nblocks: int = 4) -> dict:
    """Zero arguments of `sr_verify._kernel()` at `n` lanes, by name:
    what a test that only traces, lowers or compiles the program hands
    it."""
    import numpy as np

    from tendermint_tpu.crypto.tpu import sr_verify
    from tendermint_tpu.crypto.tpu import verify as tv

    return dict(
        ab=np.zeros((n, 32), np.uint8), rb=np.zeros((n, 32), np.uint8),
        blocks=np.zeros((n, nblocks * sr_verify._BLOCK_WORDS), np.uint32),
        counts=np.zeros(n, np.int32), s0=np.zeros((25, 2), np.uint32),
        sb=np.zeros((n, 32), np.uint8), a_pre=np.zeros(n, bool),
        r_pre=np.zeros(n, bool), s_ok=np.zeros(n, bool),
        btab=np.asarray(tv.b_comb_tables()))
