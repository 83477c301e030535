"""TPU autoscaler kernel: demand bin-packing onto node types (config #5).

Device twin of ``ray_tpu/autoscaler/demand.py`` — see its docstring for the
contract and reference citation (upstream ``ResourceDemandScheduler``,
SURVEY.md layer 11; mount empty, contract re-derived).

Phase 1 (fit onto existing nodes) IS the water-fill kernel
(``schedule_grouped`` with the first-fit threshold and
``require_available=True``).  Phase 2 is the launch loop: each iteration
first-fit-packs one virtual node of EVERY type in parallel (a ``lax.scan``
over demand classes carrying per-type used vectors), picks the best type by
(utilization score, lowest index), and batch-launches the repeat factor.
The loop is a ``lax.while_loop`` bounded by G*K + G + K + 2 iterations (the
contract's progress argument), independent of demand counts — 1M pending
demands cost the same as 1k.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..common.resources import MAX_TOTAL_CU
from ..scheduling.contract import SCALE
from .hybrid_kernel import _BIG, schedule_grouped

# Smallest fixed-point threshold above max score => first-fit traversal
# while keeping (L+1)*totals within int32 (see contract.py width audit).
FIRST_FIT_THR_FP = 2 * SCALE + 1


def _pack_all_types(type_caps, demand_reqs, remaining):
    """First-fit one fresh node of every type: (packed (K, G), used (K, R))."""
    K, R = type_caps.shape

    def step(used, xs):
        req, rem = xs
        pos = req > 0
        space = type_caps - used
        fit = jnp.where(pos[None, :],
                        space // jnp.maximum(req, 1)[None, :],
                        _BIG).min(axis=1)
        fit = jnp.clip(fit, 0, jnp.maximum(rem, 0))
        fit = jnp.where(pos.any(), fit, 0)
        return used + fit[:, None] * req[None, :], fit

    used, packed = jax.lax.scan(
        step, jnp.zeros((K, R), jnp.int32), (demand_reqs, remaining))
    return packed.T, used


def _launch_loop(type_caps, type_quotas, demand_reqs, remaining, max_iters):
    K = type_caps.shape[0]

    def cond(carry):
        remaining, quota, launches, it, done = carry
        return (remaining.sum() > 0) & ~done & (it < max_iters)

    def body(carry):
        remaining, quota, launches, it, _ = carry
        packed, used = _pack_all_types(type_caps, demand_reqs, remaining)
        score = jnp.where(type_caps > 0,
                          (used * SCALE) // jnp.maximum(type_caps, 1),
                          0).max(axis=1)
        eligible = (quota > 0) & (packed.sum(axis=1) > 0)
        s_eff = jnp.where(eligible, score, -1)
        k = jnp.argmax(s_eff).astype(jnp.int32)   # first max = lowest index
        ok = s_eff[k] >= 0
        p = packed[k]
        t = jnp.where(p > 0, remaining // jnp.maximum(p, 1), _BIG).min()
        t = jnp.maximum(jnp.minimum(t, quota[k]), 1)
        remaining = jnp.where(ok, jnp.maximum(remaining - t * p, 0),
                              remaining)
        quota = jnp.where(ok, quota.at[k].add(-t), quota)
        launches = jnp.where(ok, launches.at[k].add(t), launches)
        return remaining, quota, launches, it + 1, ~ok

    init = (remaining, type_quotas, jnp.zeros(K, jnp.int32), jnp.int32(0),
            jnp.bool_(False))
    remaining, _, launches, _, _ = jax.lax.while_loop(cond, body, init)
    return launches, remaining


@jax.jit
def autoscale(totals, avail, node_mask, demand_reqs, demand_counts,
              type_caps, type_quotas, extra_mask=None):
    """Full demand-scheduler pass on device.

    totals/avail: (N, R) int32 cu existing nodes.  node_mask: (N,) bool.
    demand_reqs: (G, R) int32.  demand_counts: (G,) int32.
    type_caps: (K, R) int32.  type_quotas: (K,) int32.
    extra_mask: optional (N,) bool beat-scoped node filter (suspect
    soft-mask) ANDed into node_mask without re-uploading it.

    Returns (launches (K,), fit_counts (G, N+1), unmet (G,), new_avail).
    Bit-identical to autoscaler.demand.get_nodes_to_launch.
    """
    if extra_mask is not None:
        node_mask = node_mask & extra_mask
    G, N = demand_reqs.shape[0], totals.shape[0]
    gmasks = jnp.ones((G, N), dtype=bool)
    fit_counts, new_avail = schedule_grouped(
        totals, avail, node_mask, demand_reqs, demand_counts, gmasks,
        jnp.int32(FIRST_FIT_THR_FP), require_available=True)
    remaining = fit_counts[:, -1]
    zero_rows = ~(demand_reqs > 0).any(axis=1)
    remaining = jnp.where(zero_rows, 0, remaining)
    K = type_caps.shape[0]
    max_iters = G * K + G + K + 2
    launches, unmet = _launch_loop(type_caps, type_quotas, demand_reqs,
                                   remaining, max_iters)
    return launches, fit_counts, unmet, new_avail


def autoscale_np(totals, avail, node_mask, demand_reqs, demand_counts,
                 type_caps, type_quotas, extra_mask=None):
    """Host wrapper: numpy in/out, device compute.

    Enforces the int32 width contract on node-type capacities: the launch
    loop computes ``used * SCALE`` in int32 (the oracle uses int64), which
    is only exact for caps within MAX_TOTAL_CU — the same bound
    ``common.resources.to_cu`` applies to real node resources.
    """
    if (np.asarray(type_caps) > MAX_TOTAL_CU).any():
        raise ValueError(
            f"type_caps exceed MAX_TOTAL_CU={MAX_TOTAL_CU} cu "
            "(int32 score-arithmetic contract)")
    out = autoscale(
        jnp.asarray(totals, jnp.int32), jnp.asarray(avail, jnp.int32),
        jnp.asarray(node_mask, bool), jnp.asarray(demand_reqs, jnp.int32),
        jnp.asarray(demand_counts, jnp.int32),
        jnp.asarray(type_caps, jnp.int32), jnp.asarray(type_quotas, jnp.int32),
        None if extra_mask is None else jnp.asarray(extra_mask, bool))
    return tuple(np.asarray(o) for o in out)
