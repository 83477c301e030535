"""Mesh-sharded delta-scheduling heartbeat engine.

``ShardedDeltaScheduler`` is the ``DeltaScheduler`` with every
node-indexed resident partitioned by rows over the two-level
("dcn", "ici") device mesh (ops/shard_reduce.py): each device holds only
its N/S node rows of the CRM mirror, its N/S key columns of the carried
(C, N) packed-key tensor, and receives only ITS shard's dirty rows per
heartbeat — the host stages per-shard upload buckets and the
double-buffered transfer to each device carries nothing another device
owns.  Global decisions (water-fill levels, the placement argmin) lower
to two-level collectives: psum/pmin over ICI within a slice, then DCN
across slices.  The beat still performs exactly ONE readback — a packed
buffer carrying both the water-fill counts and the per-(class, node)
lease budgets each shard priced from its own rows' post-beat avail
(a node-local map, so sharding it is exact; see
``ShardPlane.fused_beat``).

The aggregate mesh HBM — not one chip — now bounds the (classes x
nodes) problem: per-device resident bytes shrink by ~S, so an S-way
mesh holds a problem ~S larger than the single-chip ceiling (bench.py's
sharded stage records the model).

Counts are bit-identical to the single-device engine and the CPU oracle
at any shard count (tests/test_oracle.py randomized 2/4/8-way churn
parity); ``make_delta_scheduler`` is the dispatch-path factory that
falls back to the plain single-device ``DeltaScheduler`` whenever the
mesh resolves to one chip.
"""

from __future__ import annotations

import numpy as np

from .policy import DeltaScheduler, _bucket


class ShardedDeltaScheduler(DeltaScheduler):
    """DeltaScheduler with node rows sharded over the device mesh.

    Overrides only the device-layout hooks of the base engine: sharded
    placement of the mirror/keys/request plane, per-shard dirty-row and
    override staging, and the fused beat with the two-level ICI/DCN
    argmin reduce.  The sync protocol (epoch journal, dirty-fraction
    fallback, class slot registry, double-buffered staging parity) is
    inherited unchanged — so is the public surface, short of the SPREAD
    variant (``spread_on_device``).
    """

    spread_on_device = False

    def __init__(self, crm, n_shards: int = 0,
                 reduce_mode: str | None = None):
        import jax

        from ..common.config import get_config
        from ..ops import shard_reduce as sr
        super().__init__(crm)
        cfg = get_config()
        if reduce_mode is None:
            reduce_mode = cfg.scheduler_shard_reduce
        if n_shards <= 0:
            n_shards = cfg.scheduler_shards
        self._n_shards = sr.resolve_shards(n_shards,
                                           len(jax.local_devices()))
        self._reduce_mode = reduce_mode
        self._plane_cache = None
        self.stats["shards"] = self._n_shards

    @property
    def _plane(self):
        if self._plane_cache is None:
            from ..ops import shard_reduce as sr
            self._plane_cache = sr.plane_for(self._n_shards,
                                             self._reduce_mode)
        return self._plane_cache

    # -- device-layout hooks ------------------------------------------------
    def _node_pad(self, n_real: int) -> int:
        # the power-of-2 bucket (floor 64) always divides by the
        # power-of-2 shard count resolve_shards guarantees
        n = _bucket(n_real, 64)
        s = self._plane.n_shards
        if n % s:                                    # defensive only
            n = ((n + s - 1) // s) * s
        return n

    def _n_local(self) -> int:
        return self._n // self._plane.n_shards

    def _put_state(self, ht, ha, hm):
        import jax
        pl = self._plane
        self._totals = jax.device_put(ht, pl.sh_rows)
        self._avail = jax.device_put(ha, pl.sh_rows)
        self._mask = jax.device_put(hm, pl.sh_vec)
        self._ones = jax.device_put(np.ones(hm.shape, bool), pl.sh_vec)

    def _put_reqs(self, hr):
        import jax
        self._reqs = jax.device_put(hr, self._plane.sh_repl)

    def _full_rescore_call(self, thr):
        return self._plane.full_rescore(self._totals, self._avail,
                                        self._mask, self._reqs, thr)

    def _install_classes(self, idx, vecs, thr):
        import jax
        pl = self._plane
        self._reqs, self._keys = pl.apply_dirty_classes(
            self._totals, self._avail, self._mask, self._keys,
            self._reqs, jax.device_put(idx, pl.sh_repl),
            jax.device_put(vecs, pl.sh_repl), thr)

    def _put_extra_mask(self, emp):
        import jax
        return jax.device_put(emp, self._plane.sh_vec)

    def _fused_call(self, slots_p, counts_p, em, ov, thr,
                    require_available, loc=None, sp=None):
        import jax
        if sp is not None:
            raise NotImplementedError(
                "the sharded beat has no SPREAD program: the raylet places "
                "rounds with SPREAD groups on its host twin")
        pl = self._plane
        kw = {}
        if loc is not None:
            prefs, avoids, local_row = loc
            kw = {"pref_rows": jax.device_put(prefs, pl.sh_repl),
                  "avoid": jax.device_put(avoids, pl.sh_repl),
                  "local_row": local_row}
        return pl.fused_beat(
            self._totals, self._avail, self._mask, self._keys,
            self._reqs, jax.device_put(slots_p, pl.sh_repl),
            jax.device_put(counts_p, pl.sh_repl), em, ov[0], ov[1],
            thr, require_available=require_available, **kw)

    # -- per-shard staging --------------------------------------------------
    def _shard_buckets(self, rows):
        """Group global dirty rows into per-shard buckets of LOCAL row
        indices, padded to a common power-of-2 width so each device's
        upload is one fixed-shape (B,)/(B, R) block of ITS rows only."""
        s = self._plane.n_shards
        n_l = self._n_local()
        buckets: list[list[int]] = [[] for _ in range(s)]
        for r in rows:
            buckets[r // n_l].append(r)
        b = _bucket(max(max((len(bk) for bk in buckets), default=0), 1))
        return buckets, b, n_l

    def _delta_sync(self, rows, totals, avail, mask, thr):
        import jax
        pl = self._plane
        buckets, b, n_l = self._shard_buckets(rows)
        s = pl.n_shards
        idx = np.full((s * b,), n_l, np.int32)   # local idx; pad dropped
        rt = np.zeros((s * b, self._r), np.int32)
        ra = np.zeros((s * b, self._r), np.int32)
        rm = np.zeros((s * b,), bool)
        for si, bk in enumerate(buckets):
            if not bk:
                continue
            sl = slice(si * b, si * b + len(bk))
            idx[sl] = bk
            idx[sl] -= si * n_l
            rt[sl, :self._r_real] = totals[bk]
            ra[sl, :self._r_real] = avail[bk]
            rm[sl] = mask[bk]
        # double-buffered staging, sharded on the bucket axis: the
        # transfer to each device carries only its own shard's rows
        staged = (jax.device_put(idx, pl.sh_vec),
                  jax.device_put(rt, pl.sh_rows),
                  jax.device_put(ra, pl.sh_rows),
                  jax.device_put(rm, pl.sh_vec))
        self._stage[self._parity] = staged
        self._parity ^= 1
        self._totals, self._avail, self._mask, self._keys = \
            pl.apply_dirty_rows(self._totals, self._avail, self._mask,
                                self._keys, self._reqs, *staged, thr)

    def _pack_overrides(self, overrides):
        import jax
        pl = self._plane
        s = pl.n_shards
        n_l = self._n_local()
        if not overrides:
            if self._empty_ov is None:
                idx = np.full((s * 8,), n_l, np.int32)
                av = np.zeros((s * 8, self._r), np.int32)
                self._empty_ov = (jax.device_put(idx, pl.sh_vec),
                                  jax.device_put(av, pl.sh_rows))
            return self._empty_ov
        buckets, b, _ = self._shard_buckets(sorted(overrides))
        idx = np.full((s * b,), n_l, np.int32)
        av = np.zeros((s * b, self._r), np.int32)
        for si, bk in enumerate(buckets):
            for j, row in enumerate(bk):
                vec = overrides[row]
                idx[si * b + j] = row - si * n_l
                w = min(self._r, len(vec))
                av[si * b + j, :w] = vec[:w]
        return (jax.device_put(idx, pl.sh_vec),
                jax.device_put(av, pl.sh_rows))


def make_delta_scheduler(crm, n_shards: int | None = None,
                         reduce_mode: str | None = None):
    """The dispatch-path factory: a ``ShardedDeltaScheduler`` when the
    resolved mesh has more than one chip, the plain single-device
    ``DeltaScheduler`` otherwise (graceful fallback — on one chip there
    is nothing to shard and shard_map only adds dispatch overhead).

    ``n_shards``/``reduce_mode`` default to the ``scheduler_shards`` /
    ``scheduler_shard_reduce`` knobs.
    """
    import jax

    from ..common.config import get_config
    from ..ops.shard_reduce import resolve_shards
    cfg = get_config()
    requested = cfg.scheduler_shards if n_shards is None else n_shards
    mode = cfg.scheduler_shard_reduce if reduce_mode is None \
        else reduce_mode
    s = resolve_shards(requested, len(jax.local_devices()))
    if s <= 1:
        return DeltaScheduler(crm)
    return ShardedDeltaScheduler(crm, s, mode)
