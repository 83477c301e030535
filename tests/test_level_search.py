"""The water-fill's level search: parity on every path, and its static cost.

L* is the smallest level whose slot count covers a group.  The device
finds it with a many-way search (``hybrid_kernel._level_search``): each
step probes ways - 1 levels at once, and on a mesh each step is one
cross-chip sum.  These tests hold it to the binary search of the numpy
host twin ``schedule_group_host`` and to a plain reference that lists
every slot's level, through ``fused_beat``'s plain, localized and SPREAD
programs and the sharded program on flat and two-level meshes (the
conftest's virtual CPU devices), at the thresholds 0, 0.5 and the
first-fit threshold of ``binpack_kernel``.  Then they pin how many steps
and collectives a class step takes, read off the programs' jaxprs, so
that a round trip added back shows here.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

from ray_tpu.ops import hybrid_kernel as hk
from ray_tpu.ops import shard_reduce as sr
from ray_tpu.ops.binpack_kernel import FIRST_FIT_THR_FP
from ray_tpu.ops.hybrid_kernel import schedule_group_host
from ray_tpu.scheduling.contract import SCALE, compute_budgets, threshold_fp

N, R, G = 16, 3, 4          # rows (4 a shard on 4 shards), columns, groups
SHARDS = 4
THRESHOLDS = {"thr0": 0, "thr_half": threshold_fp(0.5),
              "first_fit": FIRST_FIT_THR_FP}


def _cluster(seed, used_lo=0.0, used_hi=0.9):
    rng = np.random.default_rng(seed)
    totals = (rng.integers(4, 33, (N, R)) * 100).astype(np.int32)
    totals[rng.random((N, R)) < 0.15] = 0
    used = rng.uniform(used_lo, used_hi, (N, R))
    avail = (totals * (1 - used)).astype(np.int32)
    reqs = (rng.integers(1, 5, (G, R)) * 100).astype(np.int32)
    reqs[rng.random((G, R)) < 0.4] = 0
    reqs[:, 0] = np.maximum(reqs[:, 0], 100)     # every class asks
    return totals, avail, np.ones(N, bool), reqs, rng


def _total_cap(totals, avail, mask, req):
    return int(_slot_caps(totals, avail, mask, req).sum())


def _slot_caps(totals, avail, mask, req):
    """Per-row count of placements that consume (the contract's m_max)."""
    pos = req > 0
    if not pos.any():
        return np.zeros(len(totals), np.int64)
    feas = np.all(np.where(pos, totals >= req, True), axis=1) & mask
    caps = np.where(pos, avail // np.maximum(req, 1), 1 << 30).min(axis=1)
    return np.where(feas, np.clip(caps, 0, 1 << 30), 0).astype(np.int64)


def _case(name):
    """(totals, avail, mask, reqs (G, R), counts (G,)) of each edge."""
    if name == "n_avail_0":             # every row full: all overflow
        totals, avail, mask, reqs, _ = _cluster(1)
        avail[:] = 0
        return totals, avail, mask, reqs, np.array([5, 7, 0, 3])
    if name == "count_0":               # padding groups around one group
        totals, avail, mask, reqs, _ = _cluster(2)
        return totals, avail, mask, reqs, np.array([0, 9, 0, 0])
    if name == "n_avail_eq_total_cap":  # every slot, up to the top level
        totals, avail, mask, reqs, _ = _cluster(3)
        cap = _total_cap(totals, avail, mask, reqs[0])
        return totals, avail, mask, reqs, np.array([cap, 0, 4, 0])
    if name == "l_star_0":              # light load: all below threshold
        totals, avail, mask, reqs, _ = _cluster(4, 0.0, 0.05)
        return totals, avail, mask, reqs, np.array([2, 3, 1, 2])
    if name == "over_capacity":         # more than fits: the queue row
        totals, avail, mask, reqs, _ = _cluster(5, 0.5, 0.95)
        cap = _total_cap(totals, avail, mask, reqs[0])
        return totals, avail, mask, reqs, np.array([cap + 9, 40, 40, 40])
    if name == "empty_request":         # consumes nothing, always fits
        totals, avail, mask, reqs, _ = _cluster(6)
        reqs[1] = 0
        return totals, avail, mask, reqs, np.array([6, 17, 5, 0])
    if name == "masked_infeasible":     # masked rows, rows too small
        totals, avail, mask, reqs, _ = _cluster(7)
        mask[:4] = False
        totals[4:8] = 50
        avail[4:8] = 50
        reqs[2] = [3200, 0, 0]          # fits only the largest rows
        return totals, avail, mask, reqs, np.array([11, 30, 5, 8])
    if name == "ties_across_shards":    # one level set on every shard
        totals, avail, mask, reqs, _ = _cluster(10)
        totals[:] = 1600
        avail[:] = 1200
        reqs[:] = [400, 0, 0]
        return totals, avail, mask, reqs, np.array([24, 5, 0, 12])
    seed = {"random_a": 8, "random_b": 9}[name]
    totals, avail, mask, reqs, rng = _cluster(seed)
    mask = rng.random(N) > 0.2
    return totals, avail, mask, reqs, rng.integers(0, 60, G)


CASES = ["n_avail_0", "count_0", "n_avail_eq_total_cap", "l_star_0",
         "over_capacity", "empty_request", "masked_infeasible",
         "ties_across_shards", "random_a", "random_b"]
PATHS = ["level", "plain", "localized", "spread", "sharded_flat",
         "sharded_two_level"]


def _reference_level(totals, avail, mask, req, count, thr):
    """L* by listing every consuming slot's eff level: slot j of row n
    scores max_i ((used_i + (j+1) r_i) * SCALE) // T_i, 0 below the
    threshold; L* is the level of the n_avail-th smallest (0 for none)."""
    caps = _slot_caps(totals, avail, mask, req)
    n_avail = min(int(count), int(caps.sum()))
    if n_avail == 0:
        return 0, caps, n_avail
    used = totals.astype(np.int64) - avail
    levels = []
    for n in np.flatnonzero(caps):
        for j in range(int(caps[n])):
            s = max(((int(used[n, i]) + (j + 1) * int(req[i])) * SCALE)
                    // int(totals[n, i]) for i in range(R) if req[i] > 0)
            levels.append(0 if s < thr else s)
    return sorted(levels)[n_avail - 1], caps, n_avail


@jax.jit
def _device_level(totals, avail, req, m_max, n_avail, thr):
    """The one-chip search on ``_schedule_group``'s own m(L)."""
    terms = hk._slot_terms(totals, avail, req)

    def covers(t):
        m = hk._slots_at_or_below(t, terms, m_max, thr)
        return m.sum(axis=-1) >= n_avail
    return hk._level_search(covers, hk._SEARCH_WAYS)


def _host(totals, avail, mask, reqs, counts, thr):
    """The host twin group by group (binary search), the water-fill rows
    and the post-beat avail."""
    av = np.asarray(avail, np.int64)
    rows = []
    for req, c in zip(reqs, counts):
        row, av = schedule_group_host(av, totals, mask, req, int(c), None,
                                      thr)
        rows.append(row)
    return np.stack(rows), av


def _one_chip(path, totals, avail, mask, reqs, counts, thr):
    i32 = np.int32
    keys = hk.full_rescore(totals, avail, mask, reqs, i32(thr))
    kw = {}
    if path == "localized":
        kw = {"pref_rows": np.full(G, -1, i32), "avoid": np.zeros(G, bool),
              "local_row": i32(0)}
    elif path == "spread":
        kw = {"spread": np.zeros(G, bool), "cursor": i32(0),
              "n_rows": i32(N)}
    packed, _ = hk.fused_beat(
        totals, avail, mask, keys, reqs, np.arange(G, dtype=i32),
        counts.astype(i32), np.ones(N, bool), np.full(8, N, i32),
        np.zeros((8, R), i32), i32(thr), **kw)
    return np.asarray(packed)


def _sharded(mode, totals, avail, mask, reqs, counts, thr):
    pl = sr.plane_for(SHARDS, mode)
    put = jax.device_put
    t, a = put(totals, pl.sh_rows), put(avail, pl.sh_rows)
    m = put(mask, pl.sh_vec)
    reqs_d = put(reqs, pl.sh_repl)
    keys = pl.full_rescore(t, a, m, reqs_d, thr)
    packed, _ = pl.fused_beat(
        t, a, m, keys, reqs_d, put(np.arange(G, dtype=np.int32), pl.sh_repl),
        put(counts.astype(np.int32), pl.sh_repl),
        put(np.ones(N, bool), pl.sh_vec),
        put(np.full(SHARDS * 8, N // SHARDS, np.int32), pl.sh_vec),
        put(np.zeros((SHARDS * 8, R), np.int32), pl.sh_rows), thr)
    return np.asarray(packed)


@pytest.mark.parametrize("thr_name", list(THRESHOLDS))
@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("case", CASES)
def test_level_search_parity(case, path, thr_name):
    thr = THRESHOLDS[thr_name]
    totals, avail, mask, reqs, counts = _case(case)
    want, want_av = _host(totals, avail, mask, reqs, counts, thr)
    if path == "level":
        av = np.asarray(avail, np.int64)
        for g in range(G):
            ref, caps, n_avail = _reference_level(totals, av, mask, reqs[g],
                                                  counts[g], thr)
            got = _device_level(totals, av.astype(np.int32), reqs[g],
                                caps.astype(np.int32), np.int32(n_avail),
                                np.int32(thr))
            assert int(got) == ref, (g, int(got), ref)
            _, av = schedule_group_host(av, totals, mask, reqs[g],
                                        int(counts[g]), None, thr)
        return
    if path.startswith("sharded"):
        packed = _sharded(path.split("_", 1)[1], totals, avail, mask, reqs,
                          counts, thr)
    else:
        packed = _one_chip(path, totals, avail, mask, reqs, counts, thr)
    np.testing.assert_array_equal(packed[:G], want)
    np.testing.assert_array_equal(
        packed[G:2 * G, :N], compute_budgets(totals, want_av, reqs,
                                             node_mask=mask))


@pytest.mark.parametrize("ways", [2, 3, 4, 8, 16, 32])
def test_level_search_reaches_every_edge(ways):
    """Any monotone predicate that holds at 2*SCALE: L* at both ends of
    [0, 2*SCALE], beside them, and at every run boundary of the first
    step, in ceil(log_ways(2*SCALE + 1)) steps."""
    top = 2 * SCALE
    strides = hk._level_strides(ways)
    assert strides[-1] == 1 and len(strides) == \
        int(np.ceil(np.log(top + 1) / np.log(ways) - 1e-9))
    edges = {0, 1, SCALE - 1, SCALE, SCALE + 1, top - 1, top}
    edges |= {k * strides[0] + d for k in range(1, ways)
              for d in (-1, 0, 1) if 0 <= k * strides[0] + d <= top}
    targets = np.array(sorted(edges), np.int32)
    found = jax.jit(jax.vmap(lambda x: hk._level_search(
        lambda t: t >= x, ways)))(targets)
    np.testing.assert_array_equal(np.asarray(found), targets)


_COLLECTIVES = {"psum", "pmin", "pmax", "all_gather", "all_to_all",
                "ppermute", "reduce_scatter", "psum_scatter"}


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for j in (v if isinstance(v, (list, tuple)) else [v]):
            if isinstance(j, ClosedJaxpr):
                yield j.jaxpr
            elif isinstance(j, Jaxpr):
                yield j


def _count(jaxpr, counts, scans, times=1):
    """Collective eqns executed by ``jaxpr`` (a scan's body counts its
    length times), and each scan's length on the way down."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in _COLLECTIVES:
            counts[name] = counts.get(name, 0) + times
        reps = eqn.params["length"] if name == "scan" else 1
        if name == "scan":
            scans.append(reps)
        for sub in _sub_jaxprs(eqn):
            _count(sub, counts, scans, times * reps)


def _class_step(jaxpr):
    """The body of the outermost scan: one class step of the beat."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            return eqn.params["jaxpr"].jaxpr
        for sub in _sub_jaxprs(eqn):
            found = _class_step(sub)
            if found is not None:
                return found
    return None


@pytest.mark.parametrize("mode", ["flat", "two_level"])
def test_sharded_class_step_collectives(mode):
    """A sharded class step: 3 level-search sums (the first with the
    total capacity; 2 in the scan), one gather pair carrying the level
    set and base masses, one argmin pmin, each over "ici" then "dcn"
    (a two-level reduce is two eqns).  The bisection took 17 sums."""
    totals, avail, mask, reqs, counts = _case("random_a")
    pl = sr.plane_for(SHARDS, mode)
    n_l = N // SHARDS
    args = (totals, avail, mask, np.zeros((G, N), np.int32), reqs,
            np.arange(G, dtype=np.int32), counts.astype(np.int32),
            np.ones(N, bool), np.full(SHARDS * 8, n_l, np.int32),
            np.zeros((SHARDS * 8, R), np.int32), np.int32(0))
    step = _class_step(jax.make_jaxpr(pl.fused_program())(*args).jaxpr)
    counts_, scans = {}, []
    _count(step, counts_, scans)
    assert scans == [2]
    assert counts_ == {"psum": 2 * (1 + 2), "all_gather": 2, "pmin": 2}


def test_one_chip_class_step_search_steps():
    """A one-chip class step: one search scan of 3 steps and no
    collective (the bisection took 15 steps)."""
    totals, avail, mask, reqs, counts = _case("random_a")
    i32 = np.int32
    args = (totals, avail, mask, np.zeros((G, N), i32), reqs,
            np.arange(G, dtype=i32), counts.astype(i32), np.ones(N, bool),
            np.full(8, N, i32), np.zeros((8, R), i32), i32(0))
    step = _class_step(jax.make_jaxpr(hk.fused_beat)(*args).jaxpr)
    counts_, scans = {}, []
    _count(step, counts_, scans)
    assert scans == [3] and counts_ == {}
    assert len(hk._level_strides(hk._SEARCH_WAYS)) == 3
    assert len(hk._level_strides(sr._SEARCH_WAYS)) == 3


@pytest.mark.parametrize("seed", range(4))
def test_floor_div_is_exact_over_the_audited_range(seed):
    """``_floor_div`` against int64 floor division: divisors r * SCALE
    for r in [1, 2**17], numerators over the whole int32 range m(L) can
    reach, and the multiples of d and their neighbours, where a rounded
    quotient would land on the wrong side."""
    rng = np.random.default_rng(seed)
    r = np.concatenate([[1, 2, 3, 2**17 - 1, 2**17],
                        rng.integers(1, 2**17 + 1, 4091)])
    d = (r * SCALE).astype(np.int64)
    k = rng.integers(-2**18, 2**19, d.shape)
    near = np.clip(k * d + rng.integers(-1, 2, d.shape), -2**31, 2**31 - 1)
    wide = rng.integers(-2**30, 2**31 - 1, d.shape)
    for num in (near, wide):
        got = hk._floor_div(jnp.asarray(num, jnp.int32),
                            jnp.asarray(d, jnp.int32),
                            1.0 / jnp.asarray(d, jnp.float32))
        np.testing.assert_array_equal(np.asarray(got), num // d)
    # a column the request leaves at 0 (``_slot_terms``): _BIG over 1
    assert int(hk._floor_div(jnp.int32(hk._BIG), jnp.int32(1),
                             jnp.float32(1.0))) == hk._BIG
