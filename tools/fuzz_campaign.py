"""Extended randomized fuzz campaign over every wire decoder.

The in-suite fuzz tests (tests/test_fuzz.py) run FIXED seeds so CI is
deterministic; this tool runs the same harness with a random seed and
a time budget — the long-tail search the reference gets from go-fuzz
nightlies.

    python tools/fuzz_campaign.py [--seconds 600] [--seed N]

Exit 0 = no decoder crashed (ValueError-family rejects are clean);
any crash prints the repro blob hex + corpus tag and exits 1.
"""

import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tests"))


def main() -> int:
    seconds = 600.0
    seed = random.SystemRandom().randrange(1 << 32)
    for i, a in enumerate(sys.argv):
        if a == "--seconds":
            seconds = float(sys.argv[i + 1])
        elif a == "--seed":
            seed = int(sys.argv[i + 1])
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    os.environ["JAX_PLATFORMS"] = "cpu"
    from tendermint_tpu.libs import jaxcache

    jaxcache.configure()

    import test_fuzz as tf

    # (decoder, tag, seeds) triples reused from the suite's harness.
    from tendermint_tpu.consensus import messages as cm
    from tendermint_tpu.evidence.reactor import decode_evidence_list
    from tendermint_tpu.types.block import Block, Commit, Header
    from tendermint_tpu.types.evidence import evidence_from_bytes
    from tendermint_tpu.types.proposal import Proposal
    from tendermint_tpu.types.vote import Vote

    import test_light_attack as tla

    ctx = tla._Ctx()
    attack_ev = tla._attack_evidence(
        ctx, tla._conflicting_block(ctx, app_hash=b"\xee" * 32))

    targets = [
        (Vote.from_bytes, "vote", [tf._vote_seed()]),
        (cm.decode_consensus_msg, "consensus-msg", tf._consensus_seeds()),
        (evidence_from_bytes, "evidence", tf._evidence_seeds()),
        (evidence_from_bytes, "light-attack", [attack_ev.to_bytes()]),
        (decode_evidence_list, "ev-list", tf._evidence_seeds()),
        (tf._decode_wal_msg, "wal", tf._wal_records()),
    ]
    # block/header seeds from the attack context's real chain
    blk = ctx.block_store.load_block(1)
    targets += [
        (Header.from_bytes, "header",
         [blk.header.to_proto().finish()]),
        (Commit.from_bytes, "commit",
         [ctx.block_store.load_seen_commit(1).to_proto().finish()]),
        (Block.from_bytes, "block", [blk.to_bytes()]),
        (Proposal.from_bytes, "proposal",
         [Proposal(height=3, round=0, pol_round=-1,
                   block_id=None, timestamp=1).to_bytes()
          if hasattr(Proposal, "to_bytes") else b""]),
    ]
    targets = [(d, t, [s for s in seeds if s]) for d, t, seeds in targets]

    rng = random.Random(seed)
    deadline = time.monotonic() + seconds
    rounds = blobs = 0
    print(f"fuzzing {len(targets)} decoders, seed={seed}, "
          f"{seconds:.0f}s budget", flush=True)
    while time.monotonic() < deadline:
        rounds += 1
        for decoder, tag, seeds in targets:
            if not seeds:
                continue
            base = rng.choice(seeds)
            for blob in _mutate(rng, base):
                blobs += 1
                try:
                    decoder(blob)
                except tf.CLEAN:
                    pass
                except Exception as e:
                    print(f"CRASH in {tag}: {type(e).__name__}: {e}")
                    print(f"repro ({len(blob)}B): {blob.hex()}")
                    return 1
    print(f"clean: {rounds} rounds, {blobs} mutated blobs, "
          f"0 crashes")
    return 0


def _mutate(rng, base: bytes):
    """A spread of structural mutations per pick."""
    n = len(base)
    out = []
    for _ in range(8):
        b = bytearray(base)
        op = rng.randrange(5)
        if op == 0 and n:  # bit flip
            i = rng.randrange(n)
            b[i] ^= 1 << rng.randrange(8)
        elif op == 1 and n:  # byte splice
            i = rng.randrange(n)
            b[i] = rng.randrange(256)
        elif op == 2:  # truncate
            b = b[: rng.randrange(n + 1)]
        elif op == 3:  # duplicate a slice
            if n:
                i = rng.randrange(n)
                j = rng.randrange(i, min(n, i + 16) + 1)
                b = b[:j] + b[i:j] + b[j:]
        else:  # append garbage
            b += bytes(rng.randrange(256)
                       for _ in range(rng.randrange(1, 9)))
        out.append(bytes(b))
    out.append(bytes(rng.randrange(256)
                     for _ in range(rng.randrange(0, 96))))
    return out


if __name__ == "__main__":
    sys.exit(main())
