"""Trusted light-block store (reference: light/store/db/db.go).

Persists verified LightBlocks keyed by height; the client resumes from
the highest trusted block after restart."""

from __future__ import annotations

import bisect
import json
import time

from ..libs import tracing
from ..state.store import _valset_from_json, _valset_to_json
from ..types.block import Commit, Header
from .types import LightBlock, SignedHeader

_PREFIX = b"lb/"


def _key(height: int) -> bytes:
    return _PREFIX + height.to_bytes(8, "big")


class LightStore:
    def __init__(self, db):
        self.db = db
        # The stored heights, ascending, read from the db by ONE scan
        # and kept in step by save / delete / prune. The light client
        # asks for the latest, the lowest and the closest height below
        # a target on every verify request; a scan of the prefix there
        # made a proxy over a long chain pay O(stored heights) a
        # request. None = not scanned yet.
        self._index: list[int] | None = None

    def _heights(self) -> list[int]:
        if self._index is None:
            self._index = [int.from_bytes(k[len(_PREFIX):], "big")
                           for k, _ in self.db.iterate_prefix(_PREFIX)]
        return self._index

    def save(self, lb: LightBlock) -> None:
        t0 = time.perf_counter_ns()
        payload = json.dumps({
            "header": lb.signed_header.header.to_proto().finish().hex(),
            "commit": lb.signed_header.commit.to_bytes().hex(),
            "validators": _valset_to_json(lb.validator_set),
        }).encode()
        with tracing.TRACER.quiet():   # db.write: the unit holds it
            self.db.set(_key(lb.height()), payload)
        hs = self._heights()
        at = bisect.bisect_left(hs, lb.height())
        if at == len(hs) or hs[at] != lb.height():
            hs.insert(at, lb.height())
        tracing.light_leaf(tracing.LIGHT_STORE_SAVE, t0)

    def get(self, height: int) -> LightBlock | None:
        raw = self.db.get(_key(height))
        if raw is None:
            return None
        d = json.loads(raw)
        return LightBlock(
            SignedHeader(Header.from_bytes(bytes.fromhex(d["header"])),
                         Commit.from_bytes(bytes.fromhex(d["commit"]))),
            _valset_from_json(d["validators"]),
        )

    def latest(self) -> LightBlock | None:
        latest_h = self.latest_height()
        return self.get(latest_h) if latest_h else None

    def latest_height(self) -> int:
        hs = self._heights()
        return hs[-1] if hs else 0

    def lowest_height(self) -> int:
        hs = self._heights()
        return hs[0] if hs else 0

    def height_before(self, height: int) -> int:
        """The highest stored height below `height`; 0 if none."""
        hs = self._heights()
        at = bisect.bisect_left(hs, height)
        return hs[at - 1] if at else 0

    def light_block_before(self, height: int) -> LightBlock | None:
        """The stored block closest below `height` (reference:
        light/store/db/db.go LightBlockBefore): what a height between
        the first and the last trusted block is verified from."""
        before = self.height_before(height)
        return self.get(before) if before else None

    def heights(self) -> list[int]:
        return list(self._heights())

    def delete(self, height: int) -> None:
        self.db.delete(_key(height))
        hs = self._heights()
        at = bisect.bisect_left(hs, height)
        if at < len(hs) and hs[at] == height:
            del hs[at]

    def prune(self, keep: int) -> None:
        hs = self._heights()
        gone = hs[:-keep] if keep else list(hs)
        for h in gone:
            self.db.delete(_key(h))
        del hs[:len(gone)]
