"""Device scheduling extensions: soft-locality and top-k water-fill.

These put the REMAINING live scheduling surfaces on device
(VERDICT r03 item 5): rounds with locality-biased tasks, lease-spillback
avoidance, and top-k sampling no longer force the host policy.

``schedule_grouped_localized`` — per-group soft node affinity (the
raylet's locality row): up to the preferred node's availability capacity
places there first, the remainder water-fills.  Bit-identical to the
sequential host path (NodeAffinity-soft per task, then hybrid fallback)
by the same argument as the grouped contract: the host consumes the
preferred node's availability task-by-task until it runs out — exactly
the floor-div capacity — and the fallback tasks form a uniform hybrid
batch (reference: locality-aware lease targeting + HybridPolicy —
SURVEY.md §2.5; mount empty).

``schedule_grouped_topk`` — the contention-spread mode
(``scheduler_top_k_fraction``): each class's tasks spread EVENLY over
its k best-keyed feasible nodes, rotated by one pinned random draw per
(seed, round, group).  DOCUMENTED DIVERGENCE from the host sampler:
the host draws per task from a Philox stream (uniform over top-k in
expectation); the device spreads exactly evenly with a random rotation
— same spreading intent, deterministic replay via the pinned seed, but
the two backends' draws differ, so top-k rounds are not bit-compared
across backends (fraction = 0 remains the bit-exact-parity mode).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .hybrid_kernel import (_BIG, _INF_KEY, _keys_one_req,
                            _localized_group)


@jax.jit
def schedule_grouped_localized(totals, avail, node_mask, group_reqs,
                               group_counts, group_masks, pref_rows,
                               thr_fp, extra_mask=None):
    """Like ``schedule_grouped`` with per-group soft locality.

    pref_rows: (G,) int32 preferred node row per group, -1 = none.
    extra_mask: optional (N,) bool beat-scoped node filter (suspect
    soft-mask) ANDed into node_mask without re-uploading it.
    Returns (counts (G, N+1), new_avail)."""
    if extra_mask is not None:
        node_mask = node_mask & extra_mask

    def step(avail, xs):
        req, count, gmask, pref = xs
        row, new_avail = _localized_group(avail, totals, node_mask, req,
                                          count, gmask, pref, thr_fp)
        return new_avail, row

    new_avail, counts = jax.lax.scan(
        step, avail, (group_reqs, group_counts, group_masks, pref_rows))
    return counts, new_avail


@partial(jax.jit, static_argnames=())
def schedule_grouped_topk(totals, avail, node_mask, group_reqs,
                          group_counts, group_masks, thr_fp, k_abs,
                          k_frac_num, k_frac_den, rng_key,
                          extra_mask=None):
    """Top-k contention spread on device (see module docstring).

    k per group = min(feasible, max(k_abs,
    ceil(feasible * k_frac_num / k_frac_den))).  Each group's tasks
    spread evenly over its k best keys with a rotated remainder;
    consuming placements are capped by per-node availability (the host
    sampler likewise only subtracts from available nodes — tasks beyond
    capacity queue without consuming)."""
    if extra_mask is not None:
        node_mask = node_mask & extra_mask
    n = totals.shape[0]

    def step(carry, xs):
        avail, key = carry
        req, count, gmask, gi = xs
        keys = _keys_one_req(totals, avail, req, thr_fp,
                             node_mask & gmask)
        feasible = keys != _INF_KEY
        nf = feasible.sum().astype(jnp.int32)
        # ceil(nf * num / den): parenthesize — unary minus binds tighter
        # than //, so -(-x)//d would floor instead
        k = jnp.maximum(k_abs, -((-nf * k_frac_num) // k_frac_den))
        k = jnp.clip(k, 1, jnp.maximum(nf, 1))
        order = jnp.argsort(keys, stable=True)      # best first
        in_topk = jnp.arange(n, dtype=jnp.int32) < k
        # even spread with a pinned random rotation for the remainder
        gkey = jax.random.fold_in(key, gi)
        offset = jax.random.randint(gkey, (), 0, jnp.maximum(k, 1))
        base = count // jnp.maximum(k, 1)
        extra_n = count - base * k
        pos = jnp.arange(n, dtype=jnp.int32)
        gets_extra = ((pos - offset) % jnp.maximum(k, 1)) < extra_n
        per_slot = jnp.where(in_topk, base + gets_extra, 0)
        counts_sorted = jnp.where(nf > 0, per_slot, 0)
        alloc = jnp.zeros(n, jnp.int32).at[order].set(counts_sorted)
        # consume only up to availability (queued tasks don't subtract)
        req_pos = req > 0
        caps = jnp.where(req_pos[None, :],
                         avail // jnp.maximum(req, 1)[None, :], _BIG)
        cap = jnp.clip(caps.min(axis=1), 0, _BIG)
        consumed = jnp.minimum(alloc, cap)
        new_avail = avail - consumed[:, None] * req[None, :]
        # no feasible node: the whole class overflows to column n
        row = jnp.where(nf > 0,
                        jnp.zeros(n + 1, jnp.int32).at[:n].set(alloc),
                        jnp.zeros(n + 1, jnp.int32).at[n].set(count))
        return (new_avail, key), row

    (new_avail, _), counts = jax.lax.scan(
        step, (avail, rng_key),
        (group_reqs, group_counts, group_masks,
         jnp.arange(group_reqs.shape[0], dtype=jnp.int32)))
    return counts, new_avail


# -- host wrappers -----------------------------------------------------------

def schedule_grouped_localized_np(totals, avail, node_mask, group_reqs,
                                  group_counts, pref_rows,
                                  group_masks=None, thr_fp=None,
                                  spread_threshold=None,
                                  extra_mask=None):
    from ..scheduling.contract import threshold_fp
    if thr_fp is None:
        thr_fp = threshold_fp(spread_threshold)
    g, n = group_reqs.shape[0], totals.shape[0]
    if group_masks is None:
        group_masks = np.ones((g, n), dtype=bool)
    counts, new_avail = schedule_grouped_localized(
        jnp.asarray(totals, jnp.int32), jnp.asarray(avail, jnp.int32),
        jnp.asarray(node_mask, bool), jnp.asarray(group_reqs, jnp.int32),
        jnp.asarray(group_counts, jnp.int32),
        jnp.asarray(group_masks, bool),
        jnp.asarray(pref_rows, jnp.int32), jnp.int32(thr_fp),
        None if extra_mask is None else jnp.asarray(extra_mask, bool))
    return np.asarray(counts), np.asarray(new_avail)


def schedule_grouped_topk_np(totals, avail, node_mask, group_reqs,
                             group_counts, seed, round_index,
                             group_masks=None, thr_fp=None,
                             spread_threshold=None, k_abs=1,
                             k_frac=0.0, extra_mask=None):
    from fractions import Fraction

    from ..scheduling.contract import threshold_fp
    if thr_fp is None:
        thr_fp = threshold_fp(spread_threshold)
    g, n = group_reqs.shape[0], totals.shape[0]
    if group_masks is None:
        group_masks = np.ones((g, n), dtype=bool)
    frac = Fraction(k_frac).limit_denominator(1 << 16)
    rng_key = jax.random.fold_in(
        jax.random.PRNGKey(int(seed)), int(round_index))
    counts, new_avail = schedule_grouped_topk(
        jnp.asarray(totals, jnp.int32), jnp.asarray(avail, jnp.int32),
        jnp.asarray(node_mask, bool), jnp.asarray(group_reqs, jnp.int32),
        jnp.asarray(group_counts, jnp.int32),
        jnp.asarray(group_masks, bool), jnp.int32(thr_fp),
        jnp.int32(max(int(k_abs), 1)),
        jnp.int32(frac.numerator), jnp.int32(max(frac.denominator, 1)),
        rng_key,
        None if extra_mask is None else jnp.asarray(extra_mask, bool))
    return np.asarray(counts), np.asarray(new_avail)
