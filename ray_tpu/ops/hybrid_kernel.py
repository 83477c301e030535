"""The TPU scheduling kernel: batched hybrid placement as dense device math.

This is BASELINE.json's north star: the per-heartbeat batch of pending tasks
evaluated as one dense (tasks x nodes x resources) computation — feasibility
mask + critical-resource-utilization score + pack/spread tie-break — instead
of the reference's per-task ``HybridSchedulingPolicy::Schedule`` calls inside
the raylet event loop (``src/ray/raylet/scheduling/policy/
hybrid_scheduling_policy.cc``, invoked from
``ClusterTaskManager::ScheduleAndDispatchTasks`` — SURVEY.md §3.2 hot loop;
reference mount empty, semantics re-derived in scheduling/contract.py).

Why not lax.scan over tasks?  Sequential semantics (task t+1 sees resources
consumed by task t) would serialize 1M tiny steps — SURVEY §7 hard part 1.
The resolution implemented here:

1.  Tasks are grouped by scheduling class (identical demand vector).  The
    reference itself drains its scheduling queue class-by-class, so this is
    semantics-preserving, not an approximation.
2.  Within one class, sequential greedy placement onto min-key nodes is a
    *merge of per-node non-decreasing key sequences*: placing on the argmin
    node only raises that node's key.  The final per-node placement counts
    are therefore a water-fill: find the smallest key level L* such that the
    total number of placement slots with key <= L* covers the group, take
    every slot strictly below L*, and hand out the remaining slots at level
    L* in traversal order (the contract's tie-break).  The per-node slot
    count at level L has a closed integer form because the score is an
    integer-linear function of the placement index j:

        s(j)   = max_i ((used_i + (j+1) r_i) * S) // T_i
        s(j)<=L  ⟺  ∀i: used_i + (j+1) r_i) * S < (L+1) T_i
                 ⟺  j+1 <= ((L+1) T_i - used_i S - 1) // (r_i S)

    so "slots with key <= L" is a vectorized O(N*R) expression and L* is a
    14-step integer binary search — no data-dependent iteration counts, no
    dynamic shapes, everything jit-compiles to one XLA program.
3.  Groups run under one lax.scan carrying ``avail`` — G steps (number of
    distinct scheduling classes, typically tens), not T steps (tasks).

All arithmetic is int32 with the width audit in scheduling/contract.py, so
results are bit-identical to the numpy oracle on any backend.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..scheduling.contract import AVAIL_SHIFT, BUDGET_CAP, SCALE, SCORE_SHIFT

# Python ints (folded into the program as literals), NOT jnp scalars: a
# closure-captured device buffer — even a scalar — was once seen to force
# a ~70ms/call synchronous mode on a remote chip for the whole process
# (ROADMAP Queue 1, item 8 checks it on a local one).
_BIG = 1 << 30
_INF_KEY = 2**31 - 1


def _keys_one_req(totals, avail, req, thr_fp, mask):
    """Packed int32 keys of one request vs all nodes (device twin of
    contract.compute_keys)."""
    n = totals.shape[0]
    req_pos = req > 0
    t = totals
    a = avail
    feas = jnp.all(jnp.where(req_pos[None, :], t >= req[None, :], True),
                   axis=1) & mask
    availb = jnp.all(jnp.where(req_pos[None, :], a >= req[None, :], True),
                     axis=1)
    denom = jnp.maximum(t, 1)
    q = t - a + req[None, :]
    s = jnp.where(req_pos[None, :], (q * SCALE) // denom, 0).max(
        axis=1, initial=0)
    eff = jnp.where(availb & (s < thr_fp), 0, s)
    key = ((~availb).astype(jnp.int32) << AVAIL_SHIFT) \
        | (eff << SCORE_SHIFT) | jnp.arange(n, dtype=jnp.int32)
    return jnp.where(feas, key, _INF_KEY)


def _slots_at_or_below(L, totals, used, req, req_pos, m_max, thr_fp):
    """m_n(L): per-node count of placement slots with eff-score key <= L.

    Threshold collapse: levels below thr_fp all equal the level-0 count
    (eff score of a sub-threshold available slot is 0).
    """
    Lp = jnp.where(L < thr_fp, thr_fp - 1, L)
    num = (Lp + 1) * totals - used * SCALE - 1          # (N, R)
    denom = jnp.maximum(req * SCALE, 1)[None, :]
    jc = jnp.clip(num // denom, 0, _BIG)
    jcount = jnp.where(req_pos[None, :], jc, _BIG).min(axis=1)
    return jnp.minimum(m_max, jcount)


def _schedule_group(avail, totals, node_mask, req, count, gmask, thr_fp,
                    require_available=False):
    """Place ``count`` identical requests; returns (counts_row (N+1,),
    new_avail)."""
    n = totals.shape[0]
    req_pos = req > 0
    any_req = req_pos.any()
    used = totals - avail

    feas = jnp.all(jnp.where(req_pos[None, :], totals >= req[None, :], True),
                   axis=1) & node_mask & gmask
    caps = jnp.where(req_pos[None, :],
                     avail // jnp.maximum(req, 1)[None, :], _BIG)
    m_max = jnp.where(feas & any_req, jnp.clip(caps.min(axis=1), 0, _BIG), 0)

    total_cap = m_max.sum()
    n_avail = jnp.minimum(count, total_cap)     # placements that consume
    overflow = count - n_avail                  # queue on best feasible

    m_of = partial(_slots_at_or_below, totals=totals, used=used, req=req,
                   req_pos=req_pos, m_max=m_max, thr_fp=thr_fp)

    # binary search smallest L in [0, 2*SCALE] with sum(m(L)) >= n_avail
    def bisect(carry, _):
        lo, hi = carry
        mid = (lo + hi) // 2
        ok = m_of(mid).sum() >= n_avail
        return (jnp.where(ok, lo, mid + 1), jnp.where(ok, mid, hi)), None

    (l_star, _), _ = jax.lax.scan(
        bisect, (jnp.int32(0), jnp.int32(2 * SCALE)), None,
        length=SCALE.bit_length() + 2)

    base = jnp.where(l_star > 0, m_of(jnp.maximum(l_star - 1, 0)), 0)
    at_level = m_of(l_star)
    extra = at_level - base
    rem = n_avail - base.sum()
    prefix = jnp.cumsum(extra) - extra          # exclusive, traversal order
    give = jnp.clip(rem - prefix, 0, extra)
    alloc = base + give                         # (N,) placements that consume

    new_avail = avail - alloc[:, None] * req[None, :]

    # overflow: all remaining tasks queue on the single best feasible node
    # computed on the post-allocation state (sequential semantics: once no
    # node is available, keys stop changing, so the argmin repeats).
    okeys = _keys_one_req(totals, new_avail, req, thr_fp, node_mask & gmask)
    onode = jnp.argmin(okeys).astype(jnp.int32)
    infeasible = okeys[onode] == _INF_KEY
    ocol = jnp.where(infeasible, n, onode)
    if require_available:
        # autoscaler fit semantics: feasible-but-unavailable overflow counts
        # as leftover (column n), never queued (oracle require_available
        # flag).  Overflow on an AVAILABLE node still places: that only
        # happens for empty requests, which consume nothing and are always
        # available (capacity never exhausts them into the overflow branch).
        o_avail = (okeys[onode] >> AVAIL_SHIFT) & 1 == 0
        ocol = jnp.where(infeasible | ~o_avail, n, onode)

    counts_row = jnp.zeros(n + 1, jnp.int32).at[:n].set(alloc)
    counts_row = counts_row.at[ocol].add(overflow)
    return counts_row, new_avail


@partial(jax.jit, static_argnames=("unroll", "require_available"))
def schedule_grouped(totals, avail, node_mask, group_reqs, group_counts,
                     group_masks, thr_fp, unroll: int = 1,
                     require_available: bool = False):
    """Batch-schedule G scheduling classes over N nodes on device.

    totals/avail: (N, R) int32 cu.  node_mask: (N,) bool.
    group_reqs: (G, R) int32.  group_counts: (G,) int32 (0 = padding row).
    group_masks: (G, N) bool (per-class affinity/label restriction).
    thr_fp: int32 scalar spread threshold in score fixed point.

    Returns (counts (G, N+1) int32, new_avail (N, R) int32).  Column N
    counts infeasible tasks.  Bit-identical to
    scheduling.oracle.schedule_grouped_oracle by construction.
    """
    def step(avail, xs):
        req, count, gmask = xs
        row, new_avail = _schedule_group(avail, totals, node_mask, req,
                                         count, gmask, thr_fp,
                                         require_available)
        return new_avail, row

    new_avail, counts = jax.lax.scan(
        step, avail, (group_reqs, group_counts, group_masks), unroll=unroll)
    return counts, new_avail


def _keys_one_req_host(totals, avail, req, thr_fp, mask):
    """Pure-numpy twin of ``_keys_one_req`` (int64 host arithmetic;
    values are int32-bounded by the contract audit, so results are
    bit-identical)."""
    n = totals.shape[0]
    req_pos = req > 0
    feas = np.where(req_pos[None, :], totals >= req[None, :],
                    True).all(axis=1) & mask
    availb = np.where(req_pos[None, :], avail >= req[None, :],
                      True).all(axis=1)
    denom = np.maximum(totals, 1)
    q = totals - avail + req[None, :]
    s = np.where(req_pos[None, :], (q * SCALE) // denom, 0).max(
        axis=1, initial=0)
    eff = np.where(availb & (s < thr_fp), 0, s)
    key = ((~availb).astype(np.int64) << AVAIL_SHIFT) \
        | (eff << SCORE_SHIFT) | np.arange(n, dtype=np.int64)
    return np.where(feas, key, np.int64(_INF_KEY))


def schedule_group_host(avail, totals, node_mask, req, count,
                        gmask=None, thr_fp=None, pref_row=-1,
                        require_available=False):
    """Pure-NUMPY water-fill for ONE scheduling class — no jit, no
    device transfer: the raylet's small-round dispatch path, where a
    per-round device round-trip would cost more than the math.  Same
    closed-form water-fill as ``_schedule_group`` (bit-identical; the
    parity suite compares all three of oracle/device/host).

    ``pref_row`` >= 0 applies the soft-locality semantics of
    ``schedule_grouped_localized``: a FEASIBLE preferred node takes the
    whole class (availability only gates consumption); fallback to the
    water-fill fires only when the preferred node is infeasible.

    Returns ``(counts_row (N+1,) int32, new_avail (N, R) int64)``;
    column N counts infeasible/queued-nowhere tasks.
    """
    from ..scheduling.contract import threshold_fp
    if thr_fp is None:
        thr_fp = threshold_fp(None)
    thr_fp = int(thr_fp)
    totals = np.asarray(totals, np.int64)
    avail = np.asarray(avail, np.int64)
    node_mask = np.asarray(node_mask, bool)
    req = np.asarray(req, np.int64)
    n = totals.shape[0]
    if gmask is None:
        gmask = np.ones(n, dtype=bool)
    req_pos = req > 0
    count = int(count)

    if pref_row is not None and pref_row >= 0:
        p = min(max(int(pref_row), 0), n - 1)
        feas_p = bool(np.where(req_pos, totals[p] >= req, True).all()
                      and node_mask[p] and gmask[p])
        m = count if feas_p else 0
        cap_p = int(np.where(req_pos, avail[p] // np.maximum(req, 1),
                             _BIG).min(initial=_BIG))
        consumed = min(m, max(cap_p, 0))
        avail2 = avail.copy()
        avail2[p] -= req * consumed
        rest, avail3 = schedule_group_host(
            avail2, totals, node_mask, req, count - m, gmask, thr_fp,
            pref_row=-1, require_available=require_available)
        rest[p] += m
        return rest, avail3

    any_req = bool(req_pos.any())
    used = totals - avail
    feas = np.where(req_pos[None, :], totals >= req[None, :],
                    True).all(axis=1) & node_mask & gmask
    caps = np.where(req_pos[None, :],
                    avail // np.maximum(req, 1)[None, :], _BIG)
    m_max = np.where(feas & any_req,
                     caps.min(axis=1).clip(0, _BIG), 0)
    total_cap = int(m_max.sum())
    n_avail = min(count, total_cap)
    overflow = count - n_avail

    denom_req = np.maximum(req * SCALE, 1)[None, :]
    used_scaled = used * SCALE

    def m_of(L):
        Lp = thr_fp - 1 if L < thr_fp else L
        num = (Lp + 1) * totals - used_scaled - 1
        jc = (num // denom_req).clip(0, _BIG)
        jcount = np.where(req_pos[None, :], jc, _BIG).min(axis=1)
        return np.minimum(m_max, jcount)

    lo, hi = 0, 2 * SCALE
    while lo < hi:
        mid = (lo + hi) // 2
        if int(m_of(mid).sum()) >= n_avail:
            hi = mid
        else:
            lo = mid + 1
    l_star = lo
    base = m_of(l_star - 1) if l_star > 0 else np.zeros(n, np.int64)
    extra = m_of(l_star) - base
    rem = n_avail - int(base.sum())
    prefix = np.cumsum(extra) - extra
    give = (rem - prefix).clip(0, extra)
    alloc = base + give
    new_avail = avail - alloc[:, None] * req[None, :]

    okeys = _keys_one_req_host(totals, new_avail, req, thr_fp,
                               node_mask & gmask)
    onode = int(np.argmin(okeys))
    infeasible = okeys[onode] == _INF_KEY
    ocol = n if infeasible else onode
    if require_available:
        o_avail = (int(okeys[onode]) >> AVAIL_SHIFT) & 1 == 0
        if infeasible or not o_avail:
            ocol = n
    counts_row = np.zeros(n + 1, np.int32)
    counts_row[:n] = alloc
    counts_row[ocol] += overflow
    return counts_row, new_avail


# -- delta-heartbeat kernels --------------------------------------------------
#
# The heartbeat keeps three residents in HBM between beats: the CRM mirror
# (totals/avail/mask), the interned class request matrix ``reqs`` (C, R),
# and the carried key tensor ``keys`` (C, N) — each class's packed placement
# keys against every node, bit-identical to contract.compute_keys on the
# mirror.  Per beat only the dirty slices move host->HBM and only the
# touched key columns/rows re-score; a beat's placement decisions come back
# in one fused counts readback (see scheduling.policy.DeltaScheduler).


@jax.jit
def full_rescore(totals, avail, mask, reqs, thr_fp):
    """(C, N) carried key tensor: every resident scheduling class scored
    against every node (vmapped device twin of contract.compute_keys)."""
    return jax.vmap(
        lambda r: _keys_one_req(totals, avail, r, thr_fp, mask))(reqs)


def _keys_cols(totals, avail, mask, reqs, idx, thr_fp):
    """Key columns for the B nodes in ``idx`` against all C classes —
    the delta rescore costs (C, B) instead of (C, N)."""
    t = totals[idx]                         # (B, R); padding idx clamps
    a = avail[idx]
    m = mask[idx]
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
        | (eff << SCORE_SHIFT) | idx.astype(jnp.int32)[None, :]
    return jnp.where(feas, key, _INF_KEY)


@jax.jit
def apply_dirty_rows(totals, avail, mask, keys, reqs, idx,
                     row_totals, row_avail, row_mask, thr_fp):
    """Scatter B dirty node rows into the device mirror and re-score ONLY
    the touched key columns.  ``idx`` entries == N are padding lanes
    (the scatter drops them; their rescored columns are dropped too).
    Returns (totals, avail, mask, keys)."""
    totals = totals.at[idx].set(row_totals, mode="drop")
    avail = avail.at[idx].set(row_avail, mode="drop")
    mask = mask.at[idx].set(row_mask, mode="drop")
    cols = _keys_cols(totals, avail, mask, reqs, idx, thr_fp)
    keys = keys.at[:, idx].set(cols, mode="drop")
    return totals, avail, mask, keys


@jax.jit
def apply_dirty_classes(totals, avail, mask, keys, reqs, idx, class_reqs,
                        thr_fp):
    """Install B new/changed scheduling classes (slots ``idx``; padding
    == C) and re-score their full key rows.  Returns (reqs, keys)."""
    reqs = reqs.at[idx].set(class_reqs, mode="drop")
    rows = jax.vmap(
        lambda r: _keys_one_req(totals, avail, r, thr_fp, mask))(class_reqs)
    keys = keys.at[idx].set(rows, mode="drop")
    return reqs, keys


@partial(jax.jit, static_argnames=("require_available",))
def fused_beat(totals, avail, mask, keys, reqs, class_slots, group_counts,
               extra_mask, ov_idx, ov_avail, thr_fp,
               require_available=False):
    """One heartbeat against the resident mirror: per-beat ephemeral row
    overrides (the raylet's planned-load debits), an extra soft mask
    (suspect avoidance), the grouped water-fill, and the per-class argmin
    of the carried key tensor — everything the host needs comes back in
    ONE counts readback per beat, not one per class.  The water-fill's
    final carry (post-beat avail) is NOT discarded: it prices the
    per-(class, node) lease budgets (contract.compute_budgets device
    twin) that ride the same readback, so the lease plane's admission
    quotas are the device's own leftover headroom, for free.

    class_slots: (G,) int32 slots into ``reqs``.  ov_idx/ov_avail:
    (B,) int32 rows + (B, R) int32 replacement avail rows applied for
    this beat only (padding idx == N; the resident mirror is untouched).
    Returns (packed (G + C, N+1) int32 — rows [:G] are the water-fill
    counts with the overflow column, rows [G:] the per-class lease
    budgets (zero overflow column) — and argmin_rows (C,) int32)."""
    avail_eff = avail.at[ov_idx].set(ov_avail, mode="drop")
    mask_eff = mask & extra_mask
    group_reqs = reqs[jnp.clip(class_slots, 0, reqs.shape[0] - 1)]
    n = totals.shape[0]
    ones = jnp.ones((n,), bool)

    def step(av, xs):
        req, count = xs
        row, new_av = _schedule_group(av, totals, mask_eff, req, count,
                                      ones, thr_fp, require_available)
        return new_av, row

    av_fin, counts = jax.lax.scan(step, avail_eff, (group_reqs, group_counts))

    # Lease budgets off the post-beat avail.  Clamp >= 0 before the floor
    # division (contract: numpy and XLA disagree on negative ``//``), and
    # price EVERY resident class, not just this beat's active groups —
    # idle repeat classes are exactly the ones the lease plane admits
    # raylet-side without asking the head.
    av_nn = jnp.maximum(av_fin, 0)

    def budget_row(req):
        pos = req > 0
        feas = jnp.all(jnp.where(pos[None, :], totals >= req[None, :], True),
                       axis=1) & mask_eff
        fill = jnp.where(pos[None, :],
                         av_nn // jnp.maximum(req, 1)[None, :],
                         BUDGET_CAP).min(axis=1, initial=BUDGET_CAP)
        return jnp.where(feas, jnp.clip(fill, 0, BUDGET_CAP), 0)

    budgets = jax.vmap(budget_row)(reqs).astype(jnp.int32)          # (C, N)
    packed = jnp.concatenate(
        [counts, jnp.pad(budgets, ((0, 0), (0, 1)))], axis=0)       # +1 col
    amin = jnp.argmin(keys, axis=1).astype(jnp.int32)
    return packed, amin


def schedule_grouped_np(totals, avail, node_mask, group_reqs, group_counts,
                        group_masks=None, thr_fp=None, spread_threshold=None):
    """Convenience host wrapper: numpy in/out, device compute."""
    from ..scheduling.contract import threshold_fp
    if thr_fp is None:
        thr_fp = threshold_fp(spread_threshold)
    g, n = group_reqs.shape[0], totals.shape[0]
    if group_masks is None:
        group_masks = np.ones((g, n), dtype=bool)
    counts, new_avail = schedule_grouped(
        jnp.asarray(totals, jnp.int32), jnp.asarray(avail, jnp.int32),
        jnp.asarray(node_mask, bool), jnp.asarray(group_reqs, jnp.int32),
        jnp.asarray(group_counts, jnp.int32), jnp.asarray(group_masks, bool),
        jnp.int32(thr_fp))
    return np.asarray(counts), np.asarray(new_avail)


_SHARDED_JIT: dict = {}


def schedule_grouped_sharded_np(totals, avail, node_mask, group_reqs,
                                group_counts, group_masks=None,
                                thr_fp=None, spread_threshold=None,
                                n_shards: int = 0,
                                reduce_mode: str = "auto"):
    """GSPMD row-sharded twin of ``schedule_grouped_np``: node rows
    partition over the two-level ("dcn", "ici") mesh
    (ops.shard_reduce) and the water-fill's global sums lower to XLA
    collectives.  Bit-identical to the single-device call; node rows
    pad to a shard multiple with mask-False rows (kernel no-ops)."""
    from ..scheduling.contract import threshold_fp
    from .shard_reduce import gspmd_plane, pad_node_rows
    if thr_fp is None:
        thr_fp = threshold_fp(spread_threshold)
    g, n = group_reqs.shape[0], totals.shape[0]
    if group_masks is None:
        group_masks = np.ones((g, n), dtype=bool)
    pl = gspmd_plane(n_shards, reduce_mode)
    pad = pad_node_rows(n, pl.n_shards)
    if pad:
        totals = np.pad(totals, ((0, pad), (0, 0)))
        avail = np.pad(avail, ((0, pad), (0, 0)))
        node_mask = np.pad(node_mask, (0, pad))
        group_masks = np.pad(group_masks, ((0, 0), (0, pad)))
    key = ("hybrid", pl.n_shards, reduce_mode, jax.default_backend())
    step = _SHARDED_JIT.get(key)
    if step is None:
        step = _SHARDED_JIT[key] = jax.jit(
            schedule_grouped, out_shardings=(pl.sh_repl, pl.sh_rows))
    counts, new_avail = step(
        jax.device_put(np.ascontiguousarray(totals, np.int32), pl.sh_rows),
        jax.device_put(np.ascontiguousarray(avail, np.int32), pl.sh_rows),
        jax.device_put(np.ascontiguousarray(node_mask, bool), pl.sh_vec),
        jax.device_put(np.ascontiguousarray(group_reqs, np.int32),
                       pl.sh_repl),
        jax.device_put(np.ascontiguousarray(group_counts, np.int32),
                       pl.sh_repl),
        jax.device_put(np.ascontiguousarray(group_masks, bool),
                       pl.sh_cols),
        jnp.int32(thr_fp))
    counts = np.asarray(counts)             # rtlint: disable=W6
    new_avail = np.asarray(new_avail)       # rtlint: disable=W6
    if pad:
        counts = np.concatenate([counts[:, :n], counts[:, -1:]], axis=1)
        new_avail = new_avail[:n]
    return counts, new_avail
