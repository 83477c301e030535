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

    so "slots with key <= L" is a vectorized O(N*R) expression.  L* is
    found by a ``_SEARCH_WAYS``-ary search over the 2*SCALE + 1 levels:
    each step evaluates the slot count at ways - 1 levels in one
    (ways-1, N, R) broadcast and keeps the one run of levels where the
    monotone "covers the group" predicate turns true — 3 dependent steps
    at 32 ways where a binary search takes 14, and exactly the L* a binary
    search returns.  No data-dependent iteration counts, no dynamic
    shapes, everything jit-compiles to one XLA program.
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
# Levels probed per step of the water-fill's level search, plus one: 32
# ways take 3 steps over the 8,193 levels (16 take 4, 8 take 5).  A step
# costs about the same at 16 ways as at 32 on a v5e, so fewer steps win;
# 32 was the fastest of 4, 8, 16 and 32.  The sharded twin
# (ops/shard_reduce.py) has its own, since each of its steps also costs
# a cross-chip sum.
_SEARCH_WAYS = 32


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


def _slot_terms(totals, avail, req):
    """The level-free terms of m(L), computed once, outside the search
    loop: the totals (N, R), used * SCALE + 1 (N, R), the divisor
    d = req * SCALE (R,) and its f32 reciprocal.  A column the request
    leaves at 0 gets totals 0, used term -_BIG and d = 1: its quotient is
    _BIG at every level, so it drops out of the min over columns."""
    pos = req > 0
    d = jnp.where(pos, req * SCALE, 1)
    return (jnp.where(pos, totals, 0),
            jnp.where(pos, (totals - avail) * SCALE + 1, -_BIG),
            d, 1.0 / d.astype(jnp.float32))


def _floor_div(num, d, inv):
    """num // d, exact, where |num // d| < 2**20 (so wherever d >= SCALE,
    by the contract's width audit) or d = 1 and num = _BIG (exact in
    f32): the f32 quotient is within 1/4 of the true one, and the int32
    remainder corrects the floor by one either way.  Costs a few vector
    ops where int32 division costs many."""
    q = jnp.floor(num.astype(jnp.float32) * inv).astype(jnp.int32)
    rem = num - q * d                       # exact: wraps back into range
    return q - (rem < 0).astype(jnp.int32) + (rem >= d).astype(jnp.int32)


def _slots_at_or_below(L, terms, m_max, thr_fp):
    """m_n(L): per-node count of placement slots with eff-score key <= L,
    shape L.shape + (N,) (a vector of levels is one broadcast);
    ``terms`` from ``_slot_terms``.

    Threshold collapse: levels below thr_fp all equal the level-0 count
    (eff score of a sub-threshold available slot is 0).  The clip to
    [0, m_max] comes after the min over columns, where it is the same.
    """
    totals, used_s, d, inv = terms
    Lp = jnp.where(L < thr_fp, thr_fp - 1, L)[..., None, None]
    num = (Lp + 1) * totals - used_s                    # (..., N, R)
    return jnp.clip(_floor_div(num, d, inv).min(axis=-1), 0, m_max)


def _level_strides(ways):
    """Static run lengths of a ``ways``-ary search over the 2*SCALE + 1
    levels [0, 2*SCALE]: step i cuts what is left into ``ways`` runs of
    ``strides[i]`` levels, the last step into single levels."""
    strides, left = [], 2 * SCALE + 1
    while left > 1:
        left = -(-left // ways)
        strides.append(left)
    return strides


def _probe_levels(lo, stride, ways):
    """The ``ways`` - 1 levels one step probes: the last of every run but
    the last, clamped to 2*SCALE (where the predicate holds, see
    ``_level_search``)."""
    t = lo + stride * jnp.arange(1, ways, dtype=jnp.int32) - 1
    return jnp.minimum(t, 2 * SCALE)


def _narrowed(lo, stride, ok):
    """The first level of the run where the monotone ``ok`` over the
    probes turns true: a run per false probe lies below it."""
    return lo + stride * (ok.shape[0] - ok.sum(dtype=jnp.int32))


def _level_search(covers, ways, lo=0, steps=0):
    """L*: the smallest level in [0, 2*SCALE] where ``covers`` holds.

    ``covers`` maps a (ways-1,) vector of increasing levels to a (ways-1,)
    bool, monotone in the level, and holds at 2*SCALE (the water-fill's
    slot count there is every slot, so it covers any ``n_avail`` <=
    ``total_cap``).  Each step of the scan calls it once; a caller that
    took the first ``steps`` steps itself resumes at its ``lo``."""
    def narrow(lo, stride):
        ok = covers(_probe_levels(lo, stride, ways))
        return _narrowed(lo, stride, ok), None

    strides = jnp.array(_level_strides(ways)[steps:], jnp.int32)
    l_star, _ = jax.lax.scan(narrow, jnp.int32(lo), strides)
    return l_star


def _schedule_group(avail, totals, node_mask, req, count, gmask, thr_fp,
                    require_available=False):
    """Place ``count`` identical requests; returns (counts_row (N+1,),
    new_avail)."""
    n = totals.shape[0]
    req_pos = req > 0
    any_req = req_pos.any()

    feas = jnp.all(jnp.where(req_pos[None, :], totals >= req[None, :], True),
                   axis=1) & node_mask & gmask
    caps = jnp.where(req_pos[None, :],
                     avail // jnp.maximum(req, 1)[None, :], _BIG)
    m_max = jnp.where(feas & any_req, jnp.clip(caps.min(axis=1), 0, _BIG), 0)

    total_cap = m_max.sum()
    n_avail = jnp.minimum(count, total_cap)     # placements that consume
    overflow = count - n_avail                  # queue on best feasible

    m_of = partial(_slots_at_or_below, terms=_slot_terms(totals, avail, req),
                   m_max=m_max, thr_fp=thr_fp)

    # smallest L in [0, 2*SCALE] with sum(m(L)) >= n_avail
    l_star = _level_search(lambda t: m_of(t).sum(axis=-1) >= n_avail,
                           _SEARCH_WAYS)

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


def _localized_group(avail, totals, node_mask, req, count, gmask, pref,
                     thr_fp, require_available=False):
    """``_schedule_group`` with soft locality: a FEASIBLE preferred row
    (``pref`` >= 0) takes every task of the group (they queue there) and
    consumes ``min(count, cap)`` of its availability; an infeasible one
    leaves the whole group to the water-fill.  Bit-identical to the host
    sequence of NodeAffinity-soft placements (``schedule_group_host``
    with ``pref_row``).  Returns (counts_row (N+1,), new_avail)."""
    n = totals.shape[0]
    p = jnp.clip(pref, 0, n - 1)
    req_pos = req > 0
    feas_p = jnp.all(jnp.where(req_pos, totals[p] >= req, True)) \
        & node_mask[p] & gmask[p]
    m = jnp.where((pref >= 0) & feas_p, count, 0).astype(jnp.int32)
    cap_p = jnp.where(req_pos, avail[p] // jnp.maximum(req, 1), _BIG).min()
    consumed = jnp.minimum(m, jnp.clip(cap_p, 0, _BIG))
    avail2 = avail.at[p].add(-req * consumed)
    rest, avail3 = _schedule_group(avail2, totals, node_mask, req,
                                   count - m, gmask, thr_fp,
                                   require_available)
    return rest.at[p].add(m), avail3


# A SPREAD row's capacity is avail // req <= MAX_TOTAL_CU = 2**17 (the
# contract's width audit), so its pass bisection needs 18 steps.
_CAP_BITS = 18


def _rotation_rank(elig, start):
    """(each row's 0-based place among the ``elig`` rows in rotation
    order ``start, start+1, ..., 0, ..., start-1``; the number of
    ``elig`` rows).  Meaningless where ``elig`` is False."""
    e = elig.astype(jnp.int32)
    before = jnp.cumsum(e) - e
    rows = jnp.arange(e.shape[0], dtype=jnp.int32)
    skipped = jnp.where(rows < start, e, 0).sum()
    total = e.sum()
    return jnp.where(rows >= start, before - skipped,
                     before + total - skipped), total


def _last_in_rotation(elig, start):
    """The last ``elig`` row in rotation order from ``start`` (0 when
    there is none)."""
    rank, total = _rotation_rank(elig, start)
    return jnp.argmax(elig & (rank == total - 1)).astype(jnp.int32)


def _spread_group(avail, cursor, totals, node_mask, req, count, n_rows):
    """One SPREAD subgroup from the round-robin ``cursor``: the closed
    form of ``SpreadSchedulingPolicy`` run task by task.

    Available phase: row ``i`` fits ``cap_i = min(avail_i // req,
    count)`` tasks (0 where masked out or infeasible; a zero request
    fits none here and goes whole to the feasible phase, where it lands
    alike).  Pass ``p`` gives one task to each row with ``cap_i >= p``
    in rotation order from the cursor, so the counts are ``min(cap_i,
    T)`` for the largest ``T`` they do not exceed the group with, plus
    one for the first rows in rotation order with ``cap_i > T``
    (bisection over ``T``); they debit ``avail``.  Feasible phase: the
    rest go round-robin over the feasible rows from the cursor the
    available phase left, with no debit.  The cursor ends one past the
    last task's row, modulo ``n_rows`` (the CRM's row count, not the
    pad).

    Returns (counts_row (N+1,) — column N counts tasks no row holds —,
    the feasible phase's counts (N,), new_avail, new cursor)."""
    n = totals.shape[0]
    req_pos = req > 0
    feas = jnp.all(jnp.where(req_pos[None, :], totals >= req[None, :], True),
                   axis=1) & node_mask
    caps = jnp.where(req_pos[None, :],
                     avail // jnp.maximum(req, 1)[None, :], _BIG).min(axis=1)
    cap = jnp.where(feas & req_pos.any(), jnp.clip(caps, 0, count), 0)
    k_avail = jnp.minimum(count, cap.sum())

    # the largest T in [0, max cap] with sum(min(cap, T)) <= k_avail
    def bisect(carry, _):
        lo, hi = carry
        mid = (lo + hi + 1) // 2
        ok = jnp.minimum(cap, mid).sum() <= k_avail
        return (jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)), None

    (t, _), _ = jax.lax.scan(bisect, (jnp.int32(0), cap.max()), None,
                             length=_CAP_BITS)
    base = jnp.minimum(cap, t)
    over = cap > t
    rank, _ = _rotation_rank(over, cursor)
    alloc = base + (over & (rank < k_avail - base.sum())).astype(jnp.int32)
    new_avail = avail - alloc[:, None] * req[None, :]
    top = alloc.max()
    last = _last_in_rotation((alloc == top) & (top > 0), cursor)
    cur = jnp.where(k_avail > 0, (last + 1) % n_rows, cursor)

    rest = count - k_avail
    n_feas = feas.sum()
    per = jnp.maximum(n_feas, 1)
    rank_f, _ = _rotation_rank(feas, cur)
    queued = jnp.where(feas, rest // per + (rank_f < rest % per), 0)
    last_f = jnp.argmax(feas & (rank_f == (rest - 1) % per))
    cur = jnp.where((rest > 0) & (n_feas > 0), (last_f + 1) % n_rows, cur)

    counts_row = jnp.zeros(n + 1, jnp.int32).at[:n].set(alloc + queued)
    counts_row = counts_row.at[n].add(jnp.where(n_feas == 0, rest, 0))
    return counts_row, queued.astype(jnp.int32), new_avail, \
        cur.astype(jnp.int32)


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


def _rotation_rank_host(elig, start):
    """Pure-numpy twin of ``_rotation_rank``."""
    e = elig.astype(np.int64)
    before = np.cumsum(e) - e
    skipped = int(e[:start].sum())
    total = int(e.sum())
    rows = np.arange(e.shape[0])
    return np.where(rows >= start, before - skipped,
                    before + total - skipped), total


def spread_group_host(avail, totals, node_mask, req, count, cursor,
                      n_rows=None):
    """Pure-NUMPY twin of ``_spread_group`` (one SPREAD subgroup from
    the round-robin ``cursor``; ``n_rows`` defaults to the row count):
    the raylet's host twin places a SPREAD subgroup with it.

    Returns ``(counts_row (N+1,) int32, feasible-phase counts (N,)
    int64, new_avail (N, R) int64, new cursor)``."""
    totals = np.asarray(totals, np.int64)
    avail = np.asarray(avail, np.int64)
    req = np.asarray(req, np.int64)
    n = totals.shape[0]
    n_rows = n if n_rows is None else int(n_rows)
    cursor, count = int(cursor) % n_rows, int(count)
    req_pos = req > 0
    feas = np.where(req_pos[None, :], totals >= req[None, :],
                    True).all(axis=1) & np.asarray(node_mask, bool)
    caps = np.where(req_pos[None, :], avail // np.maximum(req, 1)[None, :],
                    _BIG).min(axis=1)
    cap = np.where(feas & req_pos.any(), caps.clip(0, count), 0)
    k_avail = min(count, int(cap.sum()))

    lo, hi = 0, int(cap.max(initial=0))
    while lo < hi:
        mid = (lo + hi + 1) // 2
        if int(np.minimum(cap, mid).sum()) <= k_avail:
            lo = mid
        else:
            hi = mid - 1
    base = np.minimum(cap, lo)
    over = cap > lo
    rank, _ = _rotation_rank_host(over, cursor)
    alloc = base + (over & (rank < k_avail - int(base.sum())))
    new_avail = avail - alloc[:, None] * req[None, :]
    if k_avail:
        top = alloc == alloc.max()
        rank, total = _rotation_rank_host(top, cursor)
        cursor = (int(np.argmax(top & (rank == total - 1))) + 1) % n_rows

    rest = count - k_avail
    n_feas = int(feas.sum())
    queued = np.zeros(n, np.int64)
    if rest and n_feas:
        rank, _ = _rotation_rank_host(feas, cursor)
        queued = np.where(feas, rest // n_feas + (rank < rest % n_feas), 0)
        last = int(np.argmax(feas & (rank == (rest - 1) % n_feas)))
        cursor = (last + 1) % n_rows
    counts_row = np.zeros(n + 1, np.int32)
    counts_row[:n] = alloc + queued
    counts_row[n] = 0 if n_feas else rest
    return counts_row, queued, new_avail, cursor


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
               require_available=False, pref_rows=None, avoid=None,
               local_row=None, spread=None, cursor=None, n_rows=None):
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

    The localized variant (``pref_rows`` given; a program of its own,
    the plain beat's is unchanged): pref_rows (G,) int32 preferred row
    per group (-1 = none), avoid (G,) bool and the int32 scalar
    ``local_row``.  Each step is ``_localized_group``: a feasible
    preferred row takes the whole group, an avoid group keeps off
    ``local_row``, and avoid wins over a preferred row.

    The SPREAD variant (``spread`` given; a program of its own): spread
    (G,) bool marks the groups placed by ``_spread_group``'s round-robin
    fill instead of the water-fill, from the int32 scalar ``cursor``
    (rotation modulo the int32 scalar ``n_rows``), which the groups
    carry in order beside ``avail``.  Which groups are SPREAD is data:
    any mix compiles once per bucket.

    Returns (packed (G + C, N+1) int32 — rows [:G] are the water-fill
    counts with the overflow column, rows [G:] the per-class lease
    budgets (zero overflow column); the SPREAD variant appends G rows:
    each group's feasible-phase counts with, in column N, the cursor
    after the group — and argmin_rows (C,) int32)."""
    avail_eff = avail.at[ov_idx].set(ov_avail, mode="drop")
    mask_eff = mask & extra_mask
    group_reqs = reqs[jnp.clip(class_slots, 0, reqs.shape[0] - 1)]
    n = totals.shape[0]
    ones = jnp.ones((n,), bool)

    node_rows = jnp.arange(n, dtype=jnp.int32)

    def hybrid(av, req, count, pref, avd):
        if pref_rows is None:
            return _schedule_group(av, totals, mask_eff, req, count, ones,
                                   thr_fp, require_available)
        gmask = ~(avd & (node_rows == local_row))
        return _localized_group(
            av, totals, mask_eff, req, count, gmask,
            jnp.where(avd, -1, pref), thr_fp, require_available)

    if spread is None and pref_rows is None:
        def step(av, xs):
            req, count = xs
            row, new_av = hybrid(av, req, count, None, None)
            return new_av, row

        av_fin, counts = jax.lax.scan(step, avail_eff,
                                      (group_reqs, group_counts))
    elif spread is None:
        def step(av, xs):
            row, new_av = hybrid(av, *xs)
            return new_av, row

        av_fin, counts = jax.lax.scan(
            step, avail_eff, (group_reqs, group_counts, pref_rows, avoid))
    else:
        g = group_counts.shape[0]
        loc = ((jnp.full((g,), -1, jnp.int32), jnp.zeros((g,), bool))
               if pref_rows is None else (pref_rows, avoid))

        def step(carry, xs):
            av, cur = carry
            req, count, pref, avd, is_spread = xs

            def spread_fill(av, cur):
                return _spread_group(av, cur, totals, mask_eff, req, count,
                                     n_rows)

            def water_fill(av, cur):
                row, new_av = hybrid(av, req, count, pref, avd)
                return row, jnp.zeros((n,), jnp.int32), new_av, cur

            row, queued, new_av, cur = jax.lax.cond(
                is_spread, spread_fill, water_fill, av, cur)
            return (new_av, cur), (row, jnp.concatenate([queued, cur[None]]))

        (av_fin, _), (counts, spread_rows) = jax.lax.scan(
            step, (avail_eff, jnp.int32(cursor) % n_rows),
            (group_reqs, group_counts, *loc, spread))

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
    if spread is not None:
        packed = jnp.concatenate([packed, spread_rows], axis=0)
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
