"""Mesh-sharded delta-heartbeat kernels: the scheduling plane partitioned
by node shard with an explicit two-level ICI/DCN argmin reduce.

The single-device ``DeltaScheduler`` (scheduling/policy.py) keeps the whole
(classes x nodes) packed-key tensor and the CRM mirror on ONE chip — that
chip's HBM bounds the schedulable problem.  This module shards every
node-indexed resident by rows over a two-level device mesh
(``("dcn", "ici")`` — slices x chips-per-slice, the MULTICHIP_r05 dry-run
layout, degenerate shapes ``(1, S)`` on one slice and ``(1, 1)`` on one
chip), under explicit ``shard_map`` bodies rather than GSPMD so each device

- holds only its N/S node rows of totals/avail/mask,
- holds only its N/S key COLUMNS of the carried (C, N) key tensor,
- re-scores only its own shard's dirty rows, staged host->HBM as
  per-shard buckets (each device's upload carries ONLY its rows),

and the beat's global decisions lower to two collectives:

- water-fill sums: ``psum`` over "ici" (intra-slice) then "dcn", one per
  step of the level search (the ways - 1 probed levels' sums in one
  vector; the first step's also carries the class's total capacity);
- the placement argmin: each shard's local min PACKED key already carries
  the global traversal index in its low ``NODE_BITS`` bits (ties are
  impossible across nodes), so a plain ``pmin`` over "ici" then "dcn" IS
  the exact (argmin-value, global-node-index) pair reduce — no index
  bookkeeping, bit-identical to ``jnp.argmin`` on the gathered tensor.

Everything stays int32 with the contract.py width audit, so counts are
bit-identical to ``schedule_grouped_oracle`` at any shard count — the
randomized 2/4/8-way parity suite in tests/test_oracle.py holds
sharded == single-device == CPU oracle.

W6 discipline: no host<->device syncs in this module — the one sanctioned
counts readback per beat lives with the caller
(scheduling/sharded_delta.py).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..scheduling.contract import (AVAIL_SHIFT, BUDGET_CAP, MAX_NODES,
                                   SCALE, SCORE_SHIFT)
from ..util.jax_compat import shard_map_compat
from .hybrid_kernel import (_level_search, _level_strides, _narrowed,
                            _probe_levels, _slot_terms, _slots_at_or_below)

# Python ints folded as literals — NOT jnp scalars: a closure-captured
# device buffer was once seen to force a slow synchronous mode on a
# remote chip (ROADMAP Queue 1, item 8 checks it on a local one).
_BIG = 1 << 30
_INF_KEY = 2**31 - 1
_IDX_MASK = MAX_NODES - 1
# Levels probed per step of the sharded level search, plus one.  Each
# step ends in one cross-chip sum, which costs the same whatever it
# carries, so this path takes 32 ways: 3 sums a class step.
_SEARCH_WAYS = 32


def resolve_shards(requested: int, n_devices: int) -> int:
    """Effective shard count: 0 => one shard per local device, clamped
    to the device count and rounded DOWN to a power of two so the
    bucketed node axis (always a power of two >= 64) divides evenly and
    global traversal indices stay inside the packed key's NODE_BITS."""
    s = n_devices if requested <= 0 else min(requested, n_devices)
    s = max(s, 1)
    return 1 << (s.bit_length() - 1)


def build_mesh(n_shards: int, reduce_mode: str = "auto"):
    """Two-level ``("dcn", "ici")`` mesh over the first ``n_shards``
    local devices.

    ``reduce_mode``:
      - "flat": one slice — shape (1, S); the DCN axis is degenerate and
        the cross-shard reduce is a single ICI pmin/psum.
      - "two_level": force the MULTICHIP_r05 dry-run shape (2, S//2)
        (falls back to flat when S is odd or 1).
      - "auto": derive slices from the devices' ``slice_index`` when the
        platform exposes one and it tiles evenly; flat otherwise (CPU
        virtual devices and single-slice TPUs have nothing to split).
    """
    from jax.sharding import Mesh
    # local_devices, NOT devices(): in multi-process JAX the global list
    # includes non-addressable chips and device_put onto those raises
    devs = jax.local_devices()[:n_shards]
    s = len(devs)
    n_slices = 1
    if reduce_mode == "two_level":
        if s >= 2 and s % 2 == 0:
            n_slices = 2
    elif reduce_mode == "auto":
        slices = {getattr(d, "slice_index", None) for d in devs}
        if None not in slices and len(slices) > 1 \
                and s % len(slices) == 0:
            n_slices = len(slices)
    # host-side device-handle array, not data
    devgrid = np.array(devs)           # rtlint: disable=W6
    return Mesh(devgrid.reshape(n_slices, s // n_slices),
                ("dcn", "ici"))


def _psum2(x):
    """Two-level sum: fold within the slice over ICI, then across
    slices over DCN — the hierarchical reduce of the dry-run's
    ``hier_load``, here feeding the water-fill's global capacities."""
    return jax.lax.psum(jax.lax.psum(x, "ici"), "dcn")


def _pmin2(x):
    """Two-level min: ICI within a slice, DCN across slices.  On packed
    int32 keys this IS the global (argmin-value, node-index) pair
    reduce: the low NODE_BITS bits carry the global traversal index, so
    the minimum key is unique and decodes to the argmin node."""
    return jax.lax.pmin(jax.lax.pmin(x, "ici"), "dcn")


def _shard_linear_index(mesh_shape):
    """This device's position in the flattened ("dcn", "ici") row
    order — row blocks are laid out dcn-major, matching
    ``P(("dcn", "ici"))`` sharding semantics."""
    return (jax.lax.axis_index("dcn") * mesh_shape[1]
            + jax.lax.axis_index("ici"))


def _keys_block(totals_l, avail_l, mask_l, req, thr_fp, offset):
    """Packed keys of one request vs THIS shard's node rows, with the
    GLOBAL traversal index in the low bits (shard-local twin of
    hybrid_kernel._keys_one_req)."""
    n_l = totals_l.shape[0]
    req_pos = req > 0
    feas = jnp.all(jnp.where(req_pos[None, :], totals_l >= req[None, :],
                             True), axis=1) & mask_l
    availb = jnp.all(jnp.where(req_pos[None, :], avail_l >= req[None, :],
                               True), axis=1)
    denom = jnp.maximum(totals_l, 1)
    q = totals_l - avail_l + req[None, :]
    s = jnp.where(req_pos[None, :], (q * SCALE) // denom, 0).max(
        axis=1, initial=0)
    eff = jnp.where(availb & (s < thr_fp), 0, s)
    key = ((~availb).astype(jnp.int32) << AVAIL_SHIFT) \
        | (eff << SCORE_SHIFT) \
        | (offset + jnp.arange(n_l, dtype=jnp.int32))
    return jnp.where(feas, key, _INF_KEY)


def _keys_cols_block(totals_l, avail_l, mask_l, reqs, idx_l, thr_fp,
                     offset):
    """Key columns for the B LOCAL node rows in ``idx_l`` against all C
    classes — the shard's delta rescore costs (C, B) instead of
    (C, N/S).  Padding lanes (idx_l == n_local) clamp on gather and are
    dropped by the caller's scatter."""
    t = totals_l[idx_l]                     # (B, R)
    a = avail_l[idx_l]
    m = mask_l[idx_l]
    req_pos = reqs > 0                      # (C, R)
    feas = jnp.all(jnp.where(req_pos[:, None, :],
                             t[None] >= reqs[:, None, :], True),
                   axis=2) & m[None]        # (C, B)
    availb = jnp.all(jnp.where(req_pos[:, None, :],
                               a[None] >= reqs[:, None, :], True), axis=2)
    denom = jnp.maximum(t, 1)[None]
    q = t[None] - a[None] + reqs[:, None, :]
    s = jnp.where(req_pos[:, None, :], (q * SCALE) // denom, 0).max(
        axis=2, initial=0)
    eff = jnp.where(availb & (s < thr_fp), 0, s)
    key = ((~availb).astype(jnp.int32) << AVAIL_SHIFT) \
        | (eff << SCORE_SHIFT) \
        | (offset + idx_l.astype(jnp.int32))[None, :]
    return jnp.where(feas, key, _INF_KEY)


def _schedule_group_sharded(avail_l, totals_l, mask_l, req, count,
                            thr_fp, offset, my_lin, n_lin,
                            require_available):
    """Shard-local water-fill for one class: every global reduction of
    hybrid_kernel._schedule_group lowers to the two-level collectives.
    Returns (alloc_l (n_local,), inf_count scalar, new_avail_l)."""
    n_l = totals_l.shape[0]
    req_pos = req > 0
    any_req = req_pos.any()

    feas = jnp.all(jnp.where(req_pos[None, :], totals_l >= req[None, :],
                             True), axis=1) & mask_l
    caps = jnp.where(req_pos[None, :],
                     avail_l // jnp.maximum(req, 1)[None, :], _BIG)
    m_max_l = jnp.where(feas & any_req,
                        jnp.clip(caps.min(axis=1), 0, _BIG), 0)

    m_of = partial(_slots_at_or_below,
                   terms=_slot_terms(totals_l, avail_l, req),
                   m_max=m_max_l, thr_fp=thr_fp)

    # smallest L in [0, 2*SCALE] with sum(m(L)) >= n_avail, one two-level
    # sum a step.  The first step is taken here: the total capacity rides
    # in its sum, since n_avail is needed only once the sums are back.
    stride = _level_strides(_SEARCH_WAYS)[0]
    t0 = _probe_levels(jnp.int32(0), stride, _SEARCH_WAYS)
    got = _psum2(jnp.concatenate([m_of(t0).sum(axis=-1),
                                  m_max_l.sum()[None]]))
    n_avail = jnp.minimum(count, got[-1])       # got[-1]: total_cap
    overflow = count - n_avail
    lo = _narrowed(jnp.int32(0), stride, got[:-1] >= n_avail)
    l_star = _level_search(
        lambda t: _psum2(m_of(t).sum(axis=-1)) >= n_avail, _SEARCH_WAYS,
        lo=lo, steps=1)

    base_l = jnp.where(l_star > 0, m_of(jnp.maximum(l_star - 1, 0)), 0)
    at_level = m_of(l_star)
    extra_l = at_level - base_l
    # one gather of each shard's (level-set mass, base mass): the global
    # base is the base column's sum, and the global exclusive prefix over
    # traversal order is the local cumsum plus the level-set mass of
    # every PRECEDING shard (row blocks are contiguous in shard-linear
    # order, so "preceding shard" == "lower rows")
    g_ici = jax.lax.all_gather(jnp.stack([extra_l.sum(), base_l.sum()]),
                               "ici")                     # (ici, 2)
    g_all = jax.lax.all_gather(g_ici, "dcn").reshape(-1, 2)  # (S, 2)
    rem = n_avail - g_all[:, 1].sum()
    before = jnp.where(jnp.arange(n_lin) < my_lin, g_all[:, 0], 0).sum()
    prefix_l = jnp.cumsum(extra_l) - extra_l + before
    give = jnp.clip(rem - prefix_l, 0, extra_l)
    alloc_l = base_l + give

    new_avail_l = avail_l - alloc_l[:, None] * req[None, :]

    # overflow: the two-level argmin reduce.  Local packed min carries
    # the global node index; pmin over ICI then DCN is exact.
    okeys_l = _keys_block(totals_l, new_avail_l, mask_l, req, thr_fp,
                          offset)
    gmin = _pmin2(okeys_l.min(initial=_INF_KEY))
    infeasible = gmin == _INF_KEY
    onode = gmin & _IDX_MASK                     # global traversal index
    queue_ok = ~infeasible
    if require_available:
        o_avail = (gmin >> AVAIL_SHIFT) & 1 == 0
        queue_ok = queue_ok & o_avail
    # scatter the overflow into the owning shard's local column; every
    # other shard drops it (explicit bound check: a negative local
    # position must not wrap around like a numpy index)
    local_pos = onode - offset
    mine = queue_ok & (local_pos >= 0) & (local_pos < n_l)
    oadd = jnp.where(mine, overflow, 0)
    alloc_row = alloc_l.at[jnp.where(mine, local_pos, n_l)].add(
        oadd, mode="drop")
    inf_count = jnp.where(queue_ok, 0, overflow)
    return alloc_row, inf_count, new_avail_l


class ShardPlane:
    """The jitted shard_map kernel bundle for one mesh.

    Holds the mesh plus the four sharded entry points the
    ``ShardedDeltaScheduler`` drives.  Residents' layouts:

      totals/avail  (N, R)  P(("dcn","ici"), None)   rows by shard
      mask          (N,)    P(("dcn","ici"))
      keys          (C, N)  P(None, ("dcn","ici"))   key COLUMNS by shard
      reqs          (C, R)  P()                      replicated

    Per-shard host->HBM buckets (dirty rows, overrides) arrive as
    (S*B, ...) arrays sharded on the leading axis: each device's
    transfer carries exactly its own shard's B-row bucket, indexed by
    LOCAL row (padding == n_local, dropped by the scatter).
    """

    def __init__(self, mesh):
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        self.mesh = mesh
        self.n_shards = int(np.prod(mesh.devices.shape))
        self._P = P
        self.sh_rows = NamedSharding(mesh, P(("dcn", "ici"), None))
        self.sh_vec = NamedSharding(mesh, P(("dcn", "ici")))
        self.sh_cols = NamedSharding(mesh, P(None, ("dcn", "ici")))
        self.sh_repl = NamedSharding(mesh, P())
        self._smap = shard_map_compat()
        self._shape = tuple(mesh.devices.shape)
        self._full_rescore = None
        self._apply_rows = None
        self._apply_classes = None
        self._fused = {}

    # -- kernel builders (lazy: first call jits, later calls reuse) --------
    def full_rescore(self, totals, avail, mask, reqs, thr_fp):
        if self._full_rescore is None:
            P = self._P
            shape = self._shape

            def body(t_l, a_l, m_l, reqs, thr):
                n_l = t_l.shape[0]
                offset = (_shard_linear_index(shape) * n_l).astype(
                    jnp.int32)
                return jax.vmap(lambda r: _keys_block(
                    t_l, a_l, m_l, r, thr, offset))(reqs)

            self._full_rescore = jax.jit(self._smap(
                body, mesh=self.mesh,
                in_specs=(P(("dcn", "ici"), None),
                          P(("dcn", "ici"), None),
                          P(("dcn", "ici")), P(), P()),
                out_specs=P(None, ("dcn", "ici"))))
        return self._full_rescore(totals, avail, mask, reqs,
                                  jnp.int32(thr_fp))

    def apply_dirty_rows(self, totals, avail, mask, keys, reqs, idx,
                         row_totals, row_avail, row_mask, thr_fp):
        """Scatter each shard's dirty-row bucket into ITS rows and
        re-score only its touched key columns."""
        if self._apply_rows is None:
            P = self._P
            shape = self._shape

            def body(t_l, a_l, m_l, k_l, reqs, idx_l, rt_l, ra_l, rm_l,
                     thr):
                n_l = t_l.shape[0]
                offset = (_shard_linear_index(shape) * n_l).astype(
                    jnp.int32)
                t_l = t_l.at[idx_l].set(rt_l, mode="drop")
                a_l = a_l.at[idx_l].set(ra_l, mode="drop")
                m_l = m_l.at[idx_l].set(rm_l, mode="drop")
                cols = _keys_cols_block(t_l, a_l, m_l, reqs, idx_l, thr,
                                        offset)
                k_l = k_l.at[:, idx_l].set(cols, mode="drop")
                return t_l, a_l, m_l, k_l

            self._apply_rows = jax.jit(self._smap(
                body, mesh=self.mesh,
                in_specs=(P(("dcn", "ici"), None),
                          P(("dcn", "ici"), None),
                          P(("dcn", "ici")),
                          P(None, ("dcn", "ici")), P(),
                          P(("dcn", "ici")),
                          P(("dcn", "ici"), None),
                          P(("dcn", "ici"), None),
                          P(("dcn", "ici")), P()),
                out_specs=(P(("dcn", "ici"), None),
                           P(("dcn", "ici"), None),
                           P(("dcn", "ici")),
                           P(None, ("dcn", "ici")))))
        return self._apply_rows(totals, avail, mask, keys, reqs, idx,
                                row_totals, row_avail, row_mask,
                                jnp.int32(thr_fp))

    def apply_dirty_classes(self, totals, avail, mask, keys, reqs, idx,
                            class_reqs, thr_fp):
        """Install B new classes (replicated reqs scatter) and re-score
        their key rows shard-locally.  Padding idx == C."""
        if self._apply_classes is None:
            P = self._P
            shape = self._shape

            def body(t_l, a_l, m_l, k_l, reqs, idx, class_reqs, thr):
                n_l = t_l.shape[0]
                offset = (_shard_linear_index(shape) * n_l).astype(
                    jnp.int32)
                reqs = reqs.at[idx].set(class_reqs, mode="drop")
                rows_l = jax.vmap(lambda r: _keys_block(
                    t_l, a_l, m_l, r, thr, offset))(class_reqs)
                k_l = k_l.at[idx].set(rows_l, mode="drop")
                return reqs, k_l

            self._apply_classes = jax.jit(self._smap(
                body, mesh=self.mesh,
                in_specs=(P(("dcn", "ici"), None),
                          P(("dcn", "ici"), None),
                          P(("dcn", "ici")),
                          P(None, ("dcn", "ici")), P(), P(), P(), P()),
                out_specs=(P(), P(None, ("dcn", "ici")))))
        return self._apply_classes(totals, avail, mask, keys, reqs, idx,
                                   class_reqs, jnp.int32(thr_fp))

    def fused_beat(self, totals, avail, mask, keys, reqs, class_slots,
                   group_counts, extra_mask, ov_idx, ov_avail, thr_fp,
                   require_available=False, pref_rows=None, avoid=None,
                   local_row=-1):
        """One sharded heartbeat (``fused_program``) on the residents;
        with ``pref_rows`` (replicated (G,) int32) and ``avoid``
        (replicated (G,) bool) the localized variant."""
        loc = () if pref_rows is None else (pref_rows, avoid,
                                            jnp.int32(local_row))
        return self.fused_program(require_available, bool(loc))(
            totals, avail, mask, keys, reqs, class_slots, group_counts,
            extra_mask, ov_idx, ov_avail, jnp.int32(thr_fp), *loc)

    def fused_program(self, require_available=False, localized=False):
        """The jitted sharded heartbeat: per-shard ephemeral overrides + soft
        mask, the G-class water-fill scan with two-level collectives,
        and the carried-key argmin via the ICI->DCN pmin reduce.  Each
        shard also prices its own rows' per-(class, node) lease budgets
        from the scan's final avail carry (contract.compute_budgets
        twin) — a purely node-local map, so sharding it is exact.

        ``localized`` compiles the variant that takes three more inputs,
        per-group preferred rows and avoid flags and the local row
        (``hybrid_kernel.fused_beat``'s semantics): the shard that owns
        a preferred row reads its totals and avail, one cross-chip sum
        per group hands them to every shard, and only the owner debits
        the row; an avoid flag masks the local row on its owner only.

        Returns (packed (G + C, N+1) int32 REPLICATED — rows [:G] the
        water-fill counts + overflow column, rows [G:] the lease
        budgets — and amin (C,) int32 replicated); the host's single
        fetch reads one buffer, the cross-device gather happened on the
        interconnect."""
        key = (bool(require_available), bool(localized))
        if key not in self._fused:
            P = self._P
            shape = self._shape
            n_lin = self.n_shards
            req_av = key[0]

            def scan_plain(a_eff, t_l, m_eff, group_reqs, counts, thr,
                           offset, my_lin):
                def step(av_l, xs):
                    req, count = xs
                    row_l, inf_c, new_av_l = _schedule_group_sharded(
                        av_l, t_l, m_eff, req, count, thr, offset,
                        my_lin, n_lin, req_av)
                    return new_av_l, (row_l, inf_c)

                return jax.lax.scan(step, a_eff, (group_reqs, counts))

            def scan_localized(a_eff, t_l, m_eff, group_reqs, counts, thr,
                               offset, my_lin, prefs, avoids, local_row):
                n_l = t_l.shape[0]
                rows_g = offset + jnp.arange(n_l, dtype=jnp.int32)

                def step(av_l, xs):
                    req, count, pref, avd = xs
                    gm_l = m_eff & ~(avd & (rows_g == local_row))
                    pref = jnp.where(avd, -1, pref)
                    lp = pref - offset
                    mine = (pref >= 0) & (lp >= 0) & (lp < n_l)
                    lpc = jnp.clip(lp, 0, n_l - 1)
                    # the owner's row, summed over shards: totals, avail
                    # and its mask bit in one collective
                    got = _psum2(jnp.where(mine, jnp.concatenate(
                        [t_l[lpc], av_l[lpc],
                         gm_l[lpc].astype(jnp.int32)[None]]), 0))
                    r = t_l.shape[1]
                    t_p, a_p, ok_p = got[:r], got[r:2 * r], got[2 * r]
                    req_pos = req > 0
                    feas_p = jnp.all(jnp.where(req_pos, t_p >= req, True)) \
                        & (ok_p > 0)
                    m = jnp.where((pref >= 0) & feas_p, count, 0).astype(
                        jnp.int32)
                    cap_p = jnp.where(req_pos, a_p // jnp.maximum(req, 1),
                                      _BIG).min()
                    consumed = jnp.minimum(m, jnp.clip(cap_p, 0, _BIG))
                    at = jnp.where(mine, lpc, n_l)
                    av2_l = av_l.at[at].add(-req * consumed, mode="drop")
                    row_l, inf_c, new_av_l = _schedule_group_sharded(
                        av2_l, t_l, gm_l, req, count - m, thr, offset,
                        my_lin, n_lin, req_av)
                    row_l = row_l.at[at].add(m, mode="drop")
                    return new_av_l, (row_l, inf_c)

                return jax.lax.scan(step, a_eff,
                                    (group_reqs, counts, prefs, avoids))

            def body(t_l, a_l, m_l, k_l, reqs, slots, counts, em_l,
                     ovi_l, ova_l, thr, *loc):
                n_l = t_l.shape[0]
                my_lin = _shard_linear_index(shape)
                offset = (my_lin * n_l).astype(jnp.int32)
                a_eff = a_l.at[ovi_l].set(ova_l, mode="drop")
                m_eff = m_l & em_l
                group_reqs = reqs[jnp.clip(slots, 0,
                                           reqs.shape[0] - 1)]
                if loc:
                    av_fin, (alloc, inf) = scan_localized(
                        a_eff, t_l, m_eff, group_reqs, counts, thr,
                        offset, my_lin, *loc)
                else:
                    av_fin, (alloc, inf) = scan_plain(
                        a_eff, t_l, m_eff, group_reqs, counts, thr,
                        offset, my_lin)

                # shard-local lease budgets off the post-beat avail
                # (clamped >= 0 before ``//`` — contract.compute_budgets)
                av_nn = jnp.maximum(av_fin, 0)

                def budget_row(req):
                    pos = req > 0
                    feas = jnp.all(
                        jnp.where(pos[None, :], t_l >= req[None, :],
                                  True), axis=1) & m_eff
                    fill = jnp.where(
                        pos[None, :],
                        av_nn // jnp.maximum(req, 1)[None, :],
                        BUDGET_CAP).min(axis=1, initial=BUDGET_CAP)
                    return jnp.where(feas,
                                     jnp.clip(fill, 0, BUDGET_CAP), 0)

                budgets_l = jax.vmap(budget_row)(reqs).astype(
                    jnp.int32)                           # (C, n_local)
                lmin = k_l.min(axis=1, initial=_INF_KEY)     # (C,)
                gmin = _pmin2(lmin)
                amin = jnp.where(gmin == _INF_KEY, 0,
                                 gmin & _IDX_MASK).astype(jnp.int32)
                return alloc, inf, budgets_l, amin

            smapped = self._smap(
                body, mesh=self.mesh,
                in_specs=(P(("dcn", "ici"), None),
                          P(("dcn", "ici"), None),
                          P(("dcn", "ici")),
                          P(None, ("dcn", "ici")), P(), P(), P(),
                          P(("dcn", "ici")),
                          P(("dcn", "ici")),
                          P(("dcn", "ici"), None), P())
                + (P(), P(), P()) * key[1],
                out_specs=(P(None, ("dcn", "ici")), P(),
                           P(None, ("dcn", "ici")), P()))

            def wrapper(t, a, m, k, reqs, slots, counts, em, ovi, ova,
                        thr, *loc):
                alloc, inf, budgets, amin = smapped(
                    t, a, m, k, reqs, slots, counts, em, ovi, ova, thr,
                    *loc)
                return (jnp.concatenate(
                    [jnp.concatenate([alloc, inf[:, None]], axis=1),
                     jnp.pad(budgets, ((0, 0), (0, 1)))], axis=0), amin)

            self._fused[key] = jax.jit(
                wrapper,
                out_shardings=(self.sh_repl, self.sh_repl))
        return self._fused[key]


def plane_for(n_shards: int, reduce_mode: str = "auto",
              _cache: dict = {}) -> ShardPlane:      # noqa: B006
    """Process-wide ShardPlane cache: one kernel bundle per
    (shard count, reduce topology) — engines come and go per raylet,
    the compiled XLA programs should not."""
    key = (n_shards, reduce_mode, jax.default_backend())
    plane = _cache.get(key)
    if plane is None:
        plane = _cache[key] = ShardPlane(build_mesh(n_shards,
                                                    reduce_mode))
    return plane
