"""ResidentArena: persistent device-resident verify buffers reused
across launches via donated args.

The general verify path re-ships ~100 B/lane (32 B pubkey + 64 B
signature + per-lane sign-byte patches) on EVERY launch — ~1 MB of
host->device transfer per 10,240-lane commit whose share of end-to-end
latency is not measured yet (docs/PERF_NOTES.md). In
consensus the inputs barely change between launches: the pubkeys are
the validator set (changes only on ABCI valset updates), and between
two speculative launches of the same height only the lanes whose
precommits just arrived differ.

The arena therefore keeps every per-lane input array ON DEVICE:

    ab (N, 32)  pubkey rows        — uploaded once per valset change
    sb (N, 64)  signature rows     ┐
    patch/split/patch_len/group    │ spliced per arrival via ONE
    s_ok, active                   ┘ donated-jit scatter

`splice()` ships only the delta rows (the sign-byte splice points +
signatures of newly arrived votes, ~105 B/lane) and updates the
resident arrays in place: `jax.jit(..., donate_argnums=...)` lets XLA
alias the outputs onto the input buffers, so steady-state the arena
never re-transfers — or re-allocates — the other lanes. `launch()`
then verifies every active lane in one kernel combining the
structured on-device message assembly (crypto/tpu/expanded.py
assemble_core: template + per-lane timestamp patch) with the general
verify body (crypto/tpu/verify.py general_core), carrying per-lane
pubkey BYTES so no comb tables are required.

Lane 0 is a permanent KNOWN-ANSWER SENTINEL (the ed25519 breaker
probe's triple, PR-6 convention): a NaN-ing kernel fails the sentinel,
so callers detect wrong-verdict devices positively instead of trusting
garbage. Template group 0 is reserved for the sentinel's message.

Transfer accounting feeds the `speculation` metrics namespace:
`speculation_arena_bytes` (resident footprint) and
`speculation_resident_reupload_bytes_total` (what splices + per-launch
templates actually shipped) — the numbers `tools/crypto_bench.py
--resident` A/Bs against fresh-transfer launches.
"""

from __future__ import annotations

import functools

import numpy as np

from . import ledger as _ledger
from . import verify as tv
from .expanded import ExpandedKeys, assemble_core
from ...types.sign_batch import PATCH_W

# Template rows per arena (group 0 = sentinel); widths match the
# structured-path guards in expanded.py (_prepare_structured): every
# legal canonical vote fits.
GROUPS = 8
PRE_W = 128
SUF_W = 64
WIDTH = 192          # message-buffer width after the 64-byte R||A prefix
_MIN_DELTA = 8       # splice delta rows pad to powers of two from here


@functools.cache
def _splice_fn():
    """Donated scatter: every resident array in, updated array out —
    XLA aliases outputs onto the donated inputs, so a steady-state
    splice allocates nothing and uploads only the delta rows."""
    import jax

    def splice(sb, s_ok, patch, split, patch_len, group, active,
               pos, d_sb, d_sok, d_patch, d_split, d_plen, d_group):
        return (
            sb.at[pos].set(d_sb),
            s_ok.at[pos].set(d_sok),
            patch.at[pos].set(d_patch),
            split.at[pos].set(d_split),
            patch_len.at[pos].set(d_plen),
            group.at[pos].set(d_group),
            active.at[pos].set(True),
        )

    return jax.jit(splice, donate_argnums=tuple(range(7)))


@functools.cache
def _clear_fn():
    """Donated deactivate-all (sentinel lane 0 stays active)."""
    import jax
    import jax.numpy as jnp

    def clear(active):
        return jnp.zeros_like(active).at[0].set(True)

    return jax.jit(clear, donate_argnums=(0,))


@functools.cache
def _mesh_splice_fn():
    """_splice_fn over a leading device axis: ONE donated jit call
    scatters every shard's (k_local, ...) delta block into its
    resident slice — all-axis-0-sharded operands keep the scatters
    chip-local, and donation still aliases outputs onto the sharded
    input buffers."""
    import jax

    def splice(sb, s_ok, patch, split, patch_len, group, active,
               pos, d_sb, d_sok, d_patch, d_split, d_plen, d_group):
        def upd(b, p, v):
            return b.at[p].set(v)

        return (
            jax.vmap(upd)(sb, pos, d_sb),
            jax.vmap(upd)(s_ok, pos, d_sok),
            jax.vmap(upd)(patch, pos, d_patch),
            jax.vmap(upd)(split, pos, d_split),
            jax.vmap(upd)(patch_len, pos, d_plen),
            jax.vmap(upd)(group, pos, d_group),
            jax.vmap(lambda a, p: a.at[p].set(True))(active, pos),
        )

    return jax.jit(splice, donate_argnums=tuple(range(7)))


@functools.cache
def _mesh_clear_fn():
    """Donated deactivate-all (every shard's sentinel stays active)."""
    import jax
    import jax.numpy as jnp

    def clear(active):
        return jnp.zeros_like(active).at[:, 0].set(True)

    return jax.jit(clear, donate_argnums=(0,))


@functools.cache
def _mesh_arena_kernel(width: int):
    """_arena_kernel vmapped over the leading device axis: each shard
    verifies its resident block against its own sentinel, all under
    ONE jit (one trace + one compile; templates and btab replicate)."""
    import jax

    assemble = assemble_core()
    core = tv.general_core()

    @jax.jit
    def kernel(ab, sb, s_ok, active, pre, pre_len, suf, suf_len,
               patch, split, patch_len, group, btab):
        def one(ab, sb, s_ok, active, patch, split, patch_len, group):
            msg, nblocks = assemble(pre, pre_len, suf, suf_len, patch,
                                    split, patch_len, group, width)
            return core(ab, sb, msg, nblocks, s_ok, btab) & active

        return jax.vmap(one)(ab, sb, s_ok, active, patch, split,
                             patch_len, group)

    return kernel


@functools.cache
def _arena_kernel(width: int):
    """Structured assembly (expanded.assemble_core) in front of the
    general verify body (verify.general_core) over per-lane resident
    pubkey bytes; inactive lanes are masked to False on device."""
    import jax

    assemble = assemble_core()
    core = tv.general_core()

    @jax.jit
    def kernel(ab, sb, s_ok, active, pre, pre_len, suf, suf_len,
               patch, split, patch_len, group, btab):
        msg, nblocks = assemble(pre, pre_len, suf, suf_len, patch,
                                split, patch_len, group, width)
        return core(ab, sb, msg, nblocks, s_ok, btab) & active

    return kernel


def _pad_rows(a: np.ndarray, rows: int) -> np.ndarray:
    """Pad a delta array to `rows` by REPEATING row 0 — duplicate
    scatter indices then write identical values, so padding can never
    corrupt a real lane."""
    if a.shape[0] == rows:
        return a
    reps = np.repeat(a[:1], rows - a.shape[0], axis=0)
    return np.concatenate([a, reps], axis=0)


class ResidentArena:
    """Fixed-capacity device-resident lane buffers (slot 0 sentinel)."""

    def __init__(self, lanes: int, width: int = WIDTH):
        import jax.numpy as jnp

        from .. import batch as cbatch

        self.width = width
        self.capacity = ExpandedKeys._bucket(max(lanes, 2))
        n = self.capacity
        spub, smsg, ssig = cbatch._ed_probe_triple()
        assert len(smsg) <= PRE_W
        ab = np.zeros((n, 32), np.uint8)
        sb = np.zeros((n, 64), np.uint8)
        ab[0] = np.frombuffer(spub, np.uint8)
        sb[0] = np.frombuffer(ssig, np.uint8)
        s_ok = tv.s_range_ok(sb).copy()
        active = np.zeros(n, bool)
        active[0] = True
        self._ab = jnp.asarray(ab)
        self._sb = jnp.asarray(sb)
        self._s_ok = jnp.asarray(s_ok)
        self._patch = jnp.zeros((n, PATCH_W), jnp.uint8)
        self._split = jnp.zeros(n, jnp.int32)
        self._patch_len = jnp.zeros(n, jnp.int32)
        self._group = jnp.zeros(n, jnp.int32)
        self._active = jnp.asarray(active)
        # host-side template staging (small; shipped per launch)
        self.pre = np.zeros((GROUPS, PRE_W), np.uint8)
        self.pre_len = np.zeros(GROUPS, np.int32)
        self.suf = np.zeros((GROUPS, SUF_W), np.uint8)
        self.suf_len = np.zeros(GROUPS, np.int32)
        self.pre[0, :len(smsg)] = np.frombuffer(smsg, np.uint8)
        self.pre_len[0] = len(smsg)
        self.reupload_bytes = 0
        # launch-ledger accounting: bytes staged since the last launch
        # (splice deltas + templates) and a host-side active-lane
        # estimate (exact when splice slots are distinct, the
        # SpeculationPlane's usage)
        self._pending_upload = 0
        self._active_lanes = 1
        self._set_arena_gauge()

    # -- sizes / metrics ----------------------------------------------

    def arena_bytes(self) -> int:
        # .nbytes off the array metadata — NEVER np.asarray here: on
        # the CPU backend that returns a zero-copy VIEW pinning the
        # buffer, and a pinned buffer defeats donation (XLA copies
        # instead of aliasing) on every subsequent splice
        return sum(int(a.nbytes) for a in (
            self._ab, self._sb, self._s_ok, self._patch, self._split,
            self._patch_len, self._group, self._active))

    def _set_arena_gauge(self) -> None:
        try:
            from ...libs.metrics import speculation_metrics

            speculation_metrics().arena_bytes.set(self.arena_bytes())
        except Exception:  # pragma: no cover - metrics never fatal
            pass
        try:
            _ledger.register_hbm("arena", _ledger.default_device_str(),
                                 self.arena_bytes())
        except Exception:  # pragma: no cover - accounting never fatal
            pass

    def _count_reupload(self, nbytes: int) -> None:
        self.reupload_bytes += nbytes
        self._pending_upload += nbytes
        try:
            from ...libs.metrics import speculation_metrics

            speculation_metrics().reupload_bytes.inc(nbytes)
        except Exception:  # pragma: no cover - metrics never fatal
            pass

    # -- slow-path installs (valset / height changes) ------------------

    def install_keys(self, pubkeys: list[bytes], start: int = 1) -> None:
        """Upload pubkey rows for slots start..start+len-1 — once per
        validator-set change, NOT per launch (that is the point)."""
        import jax.numpy as jnp

        assert start >= 1, "slot 0 is the sentinel"
        assert start + len(pubkeys) <= self.capacity
        assert all(len(p) == 32 for p in pubkeys)
        ab = np.asarray(self._ab).copy()
        ab[start:start + len(pubkeys)] = np.frombuffer(
            b"".join(pubkeys), np.uint8).reshape(-1, 32)
        self._ab = jnp.asarray(ab)

    def set_template(self, group: int, pre: bytes, suf: bytes) -> None:
        """Stage a (pre, suf) template row (group 0 is the sentinel's).
        Templates are per height and tiny; they ship per launch."""
        assert 1 <= group < GROUPS
        assert len(pre) <= PRE_W and len(suf) <= SUF_W
        self.pre[group] = 0
        self.suf[group] = 0
        self.pre[group, :len(pre)] = np.frombuffer(pre, np.uint8)
        self.suf[group, :len(suf)] = np.frombuffer(suf, np.uint8)
        self.pre_len[group] = len(pre)
        self.suf_len[group] = len(suf)

    def deactivate_all(self) -> None:
        """New height: every lane but the sentinel goes inactive; the
        buffers themselves stay resident for the next splices."""
        self._active = _clear_fn()(self._active)
        self._active_lanes = 1

    # -- the steady-state hot path ------------------------------------

    def splice(self, slots, sig_rows: np.ndarray, patch: np.ndarray,
               split: np.ndarray, patch_len: np.ndarray,
               group: np.ndarray) -> None:
        """Splice newly arrived lanes into the resident arrays: ships
        ONLY these rows (donated scatter), ~105 B/lane."""
        k = len(slots)
        if k == 0:
            return
        pos = np.asarray(slots, np.int32)
        assert pos.min() >= 1 and pos.max() < self.capacity, \
            "slot 0 is the sentinel; slots must fit the arena"
        sig_rows = np.asarray(sig_rows, np.uint8).reshape(k, 64)
        d_sok = tv.s_range_ok(sig_rows)
        bucket = _MIN_DELTA
        while bucket < k:
            bucket <<= 1
        bucket = min(bucket, self.capacity)
        if bucket < k:  # capacity-sized delta (full re-patch)
            bucket = k
        args = [_pad_rows(a, bucket) for a in (
            pos, sig_rows, d_sok,
            np.asarray(patch, np.uint8).reshape(k, PATCH_W),
            np.asarray(split, np.int32).reshape(k),
            np.asarray(patch_len, np.int32).reshape(k),
            np.asarray(group, np.int32).reshape(k))]
        self._count_reupload(sum(int(a.nbytes) for a in args))
        self._active_lanes = min(self.capacity, self._active_lanes + k)
        (self._sb, self._s_ok, self._patch, self._split,
         self._patch_len, self._group, self._active) = _splice_fn()(
            self._sb, self._s_ok, self._patch, self._split,
            self._patch_len, self._group, self._active,
            *args)

    def launch(self) -> np.ndarray:
        """Verify every active lane (sentinel included): one kernel
        launch over the resident buffers; only the templates (~1.5 KB)
        travel host->device. Returns (capacity,) verdicts — inactive
        lanes read False; callers check verdict[0] (the sentinel)
        before trusting the rest."""
        with _ledger.launch("resident") as rec:
            rec.lanes = self._active_lanes
            rec.capacity = self.capacity
            rec.compile_hit = tv.count_compile(
                "resident", (self.capacity, self.width))
            self._count_reupload(
                int(self.pre.nbytes + self.suf.nbytes
                    + self.pre_len.nbytes + self.suf_len.nbytes))
            # delta accounting: only what splices + templates staged
            # since the last launch travelled H2D — the arena's point
            rec.bytes_h2d = self._pending_upload
            self._pending_upload = 0
            with rec.stage("dispatch"):
                out = _arena_kernel(self.width)(
                    self._ab, self._sb, self._s_ok, self._active,
                    self.pre, self.pre_len, self.suf, self.suf_len,
                    self._patch, self._split, self._patch_len,
                    self._group, tv.b_comb_tables())
            with rec.stage("exec"):
                getattr(out, "block_until_ready", lambda: None)()
            with rec.stage("readback"):
                res = np.asarray(out)
            rec.result(out)
            rec.bytes_d2h = int(res.nbytes)
            rec.ok_lanes = int(res.sum())
            rec.verdict = "ok" if bool(res[0]) else "sentinel_failed"
        return res

    # -- introspection (tests pin donation with these) -----------------

    def buffer_pointer(self, name: str = "sb"):
        """unsafe_buffer_pointer of a resident array (None when the
        backend doesn't expose it) — the donation round-trip test pins
        that a splice REUSES the buffer where the backend supports
        donation."""
        arr = getattr(self, f"_{name}")
        try:
            return arr.unsafe_buffer_pointer()
        except Exception:
            try:
                db = arr.addressable_data(0)
                return db.unsafe_buffer_pointer()
            except Exception:
                return None


class MeshResidentArena:
    """Per-device arena shards over the ('dp',) verify mesh, as ONE
    jitted program.

    Every resident array carries a leading device axis — (D, per, ...)
    sharded P('dp') — so device d physically holds only its shard's
    rows, yet splice and launch are each a SINGLE donated jit call
    (one trace + one compile total; a per-shard-objects design would
    pay D separate executables, since jit caches per device).

    Global app slots (1..capacity-1, the SpeculationPlane's
    validator_index+1 convention) round-robin across shards — app lane
    i lives on shard i % D at local slot i // D + 1 — so a commit's
    arriving precommits spread evenly and each device's steady-state
    splice receives only its ~1/D share of the ~105 B/lane deltas
    (delta rows route per shard, padded to a common per-shard bucket
    with idempotent sentinel-row writes).

    Every shard keeps its OWN known-answer sentinel at local slot 0,
    so a wrong-verdict chip is attributed individually (launch()
    records per-shard results in `sentinel_ok`) instead of the
    whole-mesh "sentinel failed somewhere" signal a single shared
    sentinel would give. The aggregate verdict array's slot 0 reads
    True only when EVERY shard's sentinel verified — callers keeping
    the single-arena `out[0]` contract stay exactly as safe."""

    def __init__(self, lanes: int, width: int = WIDTH, mesh=None):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from .. import batch as cbatch

        mesh = mesh if mesh is not None else tv.effective_mesh()
        assert mesh is not None, "MeshResidentArena needs a device mesh"
        self.mesh = mesh
        self._req_lanes = lanes
        # host mirror of installed app keys (global slot -> 32 bytes):
        # ensure_mesh() replays them into the new round-robin layout
        # when the shard set changes
        self._keys_host: dict[int, bytes] = {}
        self.devices = list(mesh.devices.flat)
        d_n = len(self.devices)
        self.n_shards = d_n
        # per-shard capacity: the app-lane share + the shard sentinel,
        # bucketed like the single arena so kernel shapes stay stable
        per = ExpandedKeys._bucket(
            max(-(-(max(lanes, 2) - 1) // d_n) + 1, 2))
        self.shard_capacity = per
        self.capacity = 1 + d_n * (per - 1)
        self.width = width
        self.sentinel_ok: list[bool] | None = None
        self._sh = NamedSharding(mesh, P("dp"))

        spub, smsg, ssig = cbatch._ed_probe_triple()
        assert len(smsg) <= PRE_W
        ab = np.zeros((d_n, per, 32), np.uint8)
        sb = np.zeros((d_n, per, 64), np.uint8)
        ab[:, 0] = np.frombuffer(spub, np.uint8)
        sb[:, 0] = np.frombuffer(ssig, np.uint8)
        # sentinel-row signature constant: splice() pads a shard's
        # delta block by re-writing its sentinel row with these exact
        # bytes, so padding rows are idempotent
        self._sent_sb = sb[0, 0].copy()
        s_ok = tv.s_range_ok(sb.reshape(-1, 64)).reshape(d_n, per)
        active = np.zeros((d_n, per), bool)
        active[:, 0] = True

        def put(x):
            return jax.device_put(jnp.asarray(x), self._sh)

        self._ab = put(ab)
        self._sb = put(sb)
        self._s_ok = put(s_ok)
        self._patch = put(np.zeros((d_n, per, PATCH_W), np.uint8))
        self._split = put(np.zeros((d_n, per), np.int32))
        self._patch_len = put(np.zeros((d_n, per), np.int32))
        self._group = put(np.zeros((d_n, per), np.int32))
        self._active = put(active)
        # host-side template staging (small; replicated per launch)
        self.pre = np.zeros((GROUPS, PRE_W), np.uint8)
        self.pre_len = np.zeros(GROUPS, np.int32)
        self.suf = np.zeros((GROUPS, SUF_W), np.uint8)
        self.suf_len = np.zeros(GROUPS, np.int32)
        self.pre[0, :len(smsg)] = np.frombuffer(smsg, np.uint8)
        self.pre_len[0] = len(smsg)
        self.reupload_bytes = 0
        self._shard_reupload = [0] * d_n
        self._pending_upload = 0
        self._active_lanes = d_n  # one sentinel per shard
        try:
            from ...libs.metrics import speculation_metrics

            speculation_metrics().arena_bytes.set(self.arena_bytes())
        except Exception:  # pragma: no cover - metrics never fatal
            pass
        try:
            per_bytes = self.arena_bytes() // d_n
            for dev in self.devices:
                _ledger.register_hbm("arena_shard", str(dev), per_bytes)
        except Exception:  # pragma: no cover - accounting never fatal
            pass

    # -- sizes / metrics ----------------------------------------------

    def arena_bytes(self) -> int:
        # array metadata only — never np.asarray (the CPU-backend view
        # would pin the buffer and defeat donation; see ResidentArena)
        return sum(int(a.nbytes) for a in (
            self._ab, self._sb, self._s_ok, self._patch, self._split,
            self._patch_len, self._group, self._active))

    def _count_reupload(self, per_device: int) -> None:
        """`per_device` bytes went to EACH device this operation."""
        self.reupload_bytes += per_device * self.n_shards
        self._pending_upload += per_device * self.n_shards
        for d in range(self.n_shards):
            self._shard_reupload[d] += per_device
        try:
            from ...libs.metrics import speculation_metrics

            speculation_metrics().reupload_bytes.inc(
                per_device * self.n_shards)
        except Exception:  # pragma: no cover - metrics never fatal
            pass

    def shard_reupload_bytes(self) -> list[int]:
        """Per-device upload accounting — what the acceptance bound
        (single-device bytes / D + per-shard template overhead) and
        `tools/crypto_bench.py --mesh` measure."""
        return list(self._shard_reupload)

    # Slot routing convention (install_keys and splice inline the
    # vectorized form): global app slot s -> shard (s-1) % D, local
    # slot (s-1) // D + 1.

    # -- slow-path installs (valset / height changes) ------------------

    def install_keys(self, pubkeys: list[bytes], start: int = 1) -> None:
        """Upload pubkey rows for global app slots start.. — once per
        validator-set change, routed to each key's home shard."""
        import jax
        import jax.numpy as jnp

        assert start >= 1, "slot 0 is the sentinel"
        assert start + len(pubkeys) <= self.capacity
        assert all(len(p) == 32 for p in pubkeys)
        for off, p in enumerate(pubkeys):
            self._keys_host[start + off] = bytes(p)
        ab = np.asarray(self._ab).copy()
        i = np.arange(start - 1, start - 1 + len(pubkeys))
        ab[i % self.n_shards, i // self.n_shards + 1] = np.frombuffer(
            b"".join(pubkeys), np.uint8).reshape(-1, 32)
        self._ab = jax.device_put(jnp.asarray(ab), self._sh)

    def set_template(self, group: int, pre: bytes, suf: bytes) -> None:
        """Stage a (pre, suf) template row (group 0 is the sentinels');
        templates replicate to every shard per launch."""
        assert 1 <= group < GROUPS
        assert len(pre) <= PRE_W and len(suf) <= SUF_W
        self.pre[group] = 0
        self.suf[group] = 0
        self.pre[group, :len(pre)] = np.frombuffer(pre, np.uint8)
        self.suf[group, :len(suf)] = np.frombuffer(suf, np.uint8)
        self.pre_len[group] = len(pre)
        self.suf_len[group] = len(suf)

    def deactivate_all(self) -> None:
        """New height: every lane but the per-shard sentinels goes
        inactive; buffers stay resident for the next splices."""
        self._active = _mesh_clear_fn()(self._active)
        self._active_lanes = self.n_shards

    def ensure_mesh(self) -> bool:
        """Re-splice the arena over the current effective mesh. When a
        per-device breaker evicts a chip (or a half-open probe
        re-admits one), the shard set changes: the arena rebuilds its
        (D', per', ...) buffers over the SURVIVORS as the same single
        donated jit program (one executable, the PR-13 constraint),
        replays the installed app keys into the new round-robin
        layout, and keeps the staged templates. Old per-device
        arena_shard HBM is released from the accounting registry.
        Splice state (signatures/patches) does NOT carry over — lanes
        come back deactivated and the speculation plane's next height
        splice repopulates them, exactly the deactivate_all contract.
        Returns True when a rebuild happened."""
        want = tv.effective_mesh()
        if want is None or want is self.mesh:
            return False
        have = [str(d) for d in self.mesh.devices.flat]
        if [str(d) for d in want.devices.flat] == have:
            self.mesh = want  # same devices, fresher mesh object
            return False
        import time as _time

        from .. import batch as cbatch

        t0 = _time.perf_counter()
        try:
            for dev in self.devices:
                _ledger.register_hbm("arena_shard", str(dev), 0)
        except Exception:  # pragma: no cover - accounting never fatal
            pass
        pre, pre_len = self.pre, self.pre_len
        suf, suf_len = self.suf, self.suf_len
        keys = dict(self._keys_host)
        reup = self.reupload_bytes
        self.__init__(self._req_lanes, self.width, mesh=want)
        self.pre, self.pre_len = pre, pre_len
        self.suf, self.suf_len = suf, suf_len
        self.reupload_bytes = reup
        # replay installed keys in contiguous runs (install_keys
        # re-fills _keys_host); slots past the new capacity — possible
        # only when bucketing inflated the OLD capacity — are dropped,
        # the same as a fresh arena sized for _req_lanes
        slots = sorted(s for s in keys if s + 1 <= self.capacity)
        run_start, run = None, []
        for s in slots + [None]:
            if run and (s is None or s != run_start + len(run)):
                self.install_keys(run, start=run_start)
                run = []
            if s is None:
                break
            if not run:
                run_start = s
            run.append(keys[s])
        dt = _time.perf_counter() - t0
        try:
            from ...libs.metrics import tpu_metrics

            tpu_metrics().reshard_seconds.observe(dt)
        except Exception:  # pragma: no cover - metrics never fatal
            pass
        cbatch.logger.warning(
            "live arena reshard: %d-lane arena rebuilt over %d "
            "shard(s) in %.3fs", self._req_lanes, self.n_shards, dt)
        return True

    # -- the steady-state hot path ------------------------------------

    def splice(self, slots, sig_rows: np.ndarray, patch: np.ndarray,
               split: np.ndarray, patch_len: np.ndarray,
               group: np.ndarray) -> None:
        """Route each arriving lane to its home shard and ship ONE
        donated scatter of (D, k_local, ...) delta blocks — per DEVICE
        upload is ~1/D of the single-arena splice. Rows padding a
        shard's block re-write its sentinel row with the sentinel's
        own constants (idempotent), so padding can never corrupt a
        real lane."""
        k = len(slots)
        if k == 0:
            return
        d_n = self.n_shards
        sig_rows = np.asarray(sig_rows, np.uint8).reshape(k, 64)
        d_sok = tv.s_range_ok(sig_rows)
        patch = np.asarray(patch, np.uint8).reshape(k, PATCH_W)
        split = np.asarray(split, np.int32).reshape(k)
        patch_len = np.asarray(patch_len, np.int32).reshape(k)
        group = np.asarray(group, np.int32).reshape(k)
        # vectorized slot -> (shard, local) routing (the round-robin
        # convention above): ~10k Python iterations per full-commit
        # splice otherwise
        i = np.asarray(slots, np.int64) - 1
        assert i.size and i.min() >= 0 and i.max() < self.capacity - 1, \
            "slot 0 is the sentinel; slots must fit the arena"
        home = (i % d_n).astype(np.int64)
        local = (i // d_n + 1).astype(np.int32)
        order = np.argsort(home, kind="stable")
        counts = np.bincount(home, minlength=d_n)
        k_max = int(counts.max())
        bucket = _MIN_DELTA
        while bucket < k_max:
            bucket <<= 1
        bucket = min(bucket, self.shard_capacity)
        if bucket < k_max:  # capacity-sized delta (full re-patch)
            bucket = k_max
        pos = np.zeros((d_n, bucket), np.int32)
        v_sb = np.tile(self._sent_sb, (d_n, bucket, 1))
        v_sok = np.ones((d_n, bucket), bool)
        v_patch = np.zeros((d_n, bucket, PATCH_W), np.uint8)
        v_split = np.zeros((d_n, bucket), np.int32)
        v_plen = np.zeros((d_n, bucket), np.int32)
        v_group = np.zeros((d_n, bucket), np.int32)
        off = 0
        for d in range(d_n):
            m = int(counts[d])
            if not m:
                continue
            sel = order[off:off + m]
            off += m
            pos[d, :m] = local[sel]
            v_sb[d, :m] = sig_rows[sel]
            v_sok[d, :m] = d_sok[sel]
            v_patch[d, :m] = patch[sel]
            v_split[d, :m] = split[sel]
            v_plen[d, :m] = patch_len[sel]
            v_group[d, :m] = group[sel]
        per_dev = sum(int(a.nbytes) for a in (
            pos, v_sb, v_sok, v_patch, v_split, v_plen,
            v_group)) // d_n
        self._count_reupload(per_dev)
        self._active_lanes = min(self.capacity + d_n - 1,
                                 self._active_lanes + k)
        sh = self._sh
        import jax

        args = [jax.device_put(a, sh) for a in (
            pos, v_sb, v_sok, v_patch, v_split, v_plen, v_group)]
        (self._sb, self._s_ok, self._patch, self._split,
         self._patch_len, self._group, self._active) = \
            _mesh_splice_fn()(
                self._sb, self._s_ok, self._patch, self._split,
                self._patch_len, self._group, self._active, *args)

    def launch(self) -> np.ndarray:
        """ONE vmapped kernel over every shard's resident block (the
        per-device programs run concurrently under the single jit
        dispatch). Returns (capacity,) verdicts in GLOBAL slot order;
        `sentinel_ok` holds each shard's known-answer result for
        per-device attribution. Slot 0 of the returned array is the
        conjunction of every shard sentinel."""
        d_n = self.n_shards
        with _ledger.launch("resident_mesh") as rec:
            rec.lanes = self._active_lanes
            rec.capacity = 1 + d_n * (self.shard_capacity - 1)
            rec.n_devices = d_n
            rec.shard_lanes = [self.shard_capacity] * d_n
            rec.compile_hit = tv.count_compile(
                "resident_mesh",
                (d_n, self.shard_capacity, self.width))
            self._count_reupload(
                int(self.pre.nbytes + self.suf.nbytes
                    + self.pre_len.nbytes + self.suf_len.nbytes))
            rec.bytes_h2d = self._pending_upload
            self._pending_upload = 0
            with rec.stage("dispatch"):
                out = _mesh_arena_kernel(self.width)(
                    self._ab, self._sb, self._s_ok, self._active,
                    self.pre, self.pre_len, self.suf, self.suf_len,
                    self._patch, self._split, self._patch_len,
                    self._group, tv.b_comb_tables())
            with rec.stage("exec"):
                getattr(out, "block_until_ready", lambda: None)()
            with rec.stage("readback"):
                o = np.asarray(out)  # (D, per)
            rec.result(out)
            rec.bytes_d2h = int(o.nbytes)
            self.sentinel_ok = [bool(o[d, 0]) for d in range(d_n)]
            verd = np.zeros(self.capacity, bool)
            verd[0] = all(self.sentinel_ok)
            for d in range(d_n):
                verd[1 + d::d_n] = o[d, 1:]
            rec.ok_lanes = int(verd.sum())
            rec.verdict = ("ok" if all(self.sentinel_ok)
                           else "sentinel_failed")
        try:
            from ...libs.metrics import tpu_metrics

            tmet = tpu_metrics()
            for d in range(d_n):
                tmet.shard_lanes.inc(self.shard_capacity,
                                     device=str(d))
        except Exception:  # pragma: no cover - metrics never fatal
            pass
        return verd

    def failed_shards(self) -> list[tuple[int, str]]:
        """(shard index, device) of every sentinel that failed on the
        last launch — the per-device breaker attribution detail."""
        if self.sentinel_ok is None:
            return []
        return [(i, str(self.devices[i]))
                for i, ok in enumerate(self.sentinel_ok) if not ok]

    def buffer_pointer(self, name: str = "sb", shard: int = 0):
        """unsafe_buffer_pointer of one shard's slice of a resident
        array (donation round-trip pinning, like ResidentArena's)."""
        arr = getattr(self, f"_{name}")
        try:
            return arr.addressable_data(shard).unsafe_buffer_pointer()
        except Exception:
            return None


# Per-device arena shards on/off (the [mesh] config section's
# arena_shards knob, wired by node._build; default on — a mesh that
# exists should be used).
_ARENA_SHARDS = True


def set_arena_shards(on: bool) -> None:
    global _ARENA_SHARDS
    _ARENA_SHARDS = bool(on)


def make_arena(lanes: int, width: int = WIDTH):
    """The speculation plane's arena factory: per-device shards when a
    mesh exists (and [mesh] arena_shards is on), the classic
    single-device arena otherwise."""
    mesh = tv.effective_mesh()
    if _ARENA_SHARDS and mesh is not None:
        return MeshResidentArena(lanes, width, mesh=mesh)
    return ResidentArena(lanes, width)


def load_programs(arena) -> int:
    """Run once everything an arena's steady state launches, on an
    arena that holds nothing yet: a splice of every delta bucket
    (powers of two from _MIN_DELTA up to the capacity), the verify
    launch at capacity, the clear. For whoever would rather pay the
    compiles before the first precommit than at it (the speculation
    plane, when consensus starts). The lanes spliced verify nothing
    and are inactive again afterwards. Returns the programs run."""
    sizes, k = [], _MIN_DELTA
    while k < arena.capacity - 1:
        sizes.append(k)
        k <<= 1
    sizes.append(arena.capacity - 1)
    for k in sizes:
        # as a height does it: the clear, then splices; a splice takes
        # the buffers the clear or another splice left (on a mesh their
        # placements are part of the program's key)
        arena.deactivate_all()
        for _ in range(2):
            arena.splice(
                np.arange(1, k + 1), np.zeros((k, 64), np.uint8),
                np.zeros((k, PATCH_W), np.uint8), np.zeros(k, np.int32),
                np.zeros(k, np.int32), np.zeros(k, np.int32))
    with _ledger.workload("probe"):
        arena.launch()
    arena.deactivate_all()
    return len(sizes) + 2
