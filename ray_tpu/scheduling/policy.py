"""The SchedulingPolicy plugin boundary and its stock policies.

Reference parity: ``ISchedulingPolicy::Schedule(resource_request,
SchedulingOptions)`` with implementations ``HybridSchedulingPolicy``,
``SpreadSchedulingPolicy``, ``RandomSchedulingPolicy``,
``NodeAffinitySchedulingPolicy``, ``NodeLabelSchedulingPolicy``, composed by
``CompositeSchedulingPolicy`` (``src/ray/raylet/scheduling/policy/*``).
[SURVEY.md §1 layer 5; mount empty.]  BASELINE.json gates the TPU backend
behind exactly this boundary: the hybrid policy here can answer from the CPU
oracle or defer batches to the device kernel — callers cannot tell which.

Policies are pure functions of (ClusterState snapshot, request, options):
no hidden state except the documented RNG/round-robin cursors, so parity is a
property test (SURVEY §4 closing note).
"""

from __future__ import annotations

import enum
from dataclasses import dataclass

import numpy as np

from ..util.tracing import phase
from .contract import (AVAIL_SHIFT, INFEASIBLE_KEY, compute_keys,
                       threshold_fp)
from .oracle import ClusterState


class SchedulingType(enum.Enum):
    HYBRID = 0
    SPREAD = 1
    RANDOM = 2
    NODE_AFFINITY = 3
    NODE_LABEL = 4


@dataclass
class SchedulingOptions:
    """Mirror of the reference's SchedulingOptions variants."""

    scheduling_type: SchedulingType = SchedulingType.HYBRID
    spread_threshold: float | None = None      # None => config default
    avoid_local_node: bool = False
    local_node_row: int = 0                    # row of the scheduling raylet
    require_node_available: bool = False
    # NODE_AFFINITY
    node_row: int = -1
    soft: bool = False
    # label constraints resolved by the caller into a node mask
    node_mask: np.ndarray | None = None


class ISchedulingPolicy:
    def schedule(self, state: ClusterState, req: np.ndarray,
                 options: SchedulingOptions) -> int:
        """Return node row or -1. Must not mutate ``state`` unless the
        placement consumes resources (available-bucket placements do)."""
        raise NotImplementedError


class HybridSchedulingPolicy(ISchedulingPolicy):
    """The default policy — contract.py semantics (SURVEY §2.5).

    Top-k sampling (reference ``scheduler_top_k_fraction`` /
    ``scheduler_top_k_absolute``): with fraction > 0 the policy samples
    uniformly among the k best-keyed feasible nodes instead of always
    taking the minimum, trading determinism for contention spread.  The
    stream is a pinned Philox counter (one draw per decision) so runs
    replay bit-for-bit.  fraction = 0 (the default) is the
    argmin/bit-exact-parity configuration; the device batch path requires
    it (sampling rounds route through this host policy)."""

    def __init__(self, seed: int = 0):
        self._rng = np.random.Generator(np.random.Philox(seed))

    def schedule(self, state, req, options):
        from ..common.config import get_config
        thr = threshold_fp(options.spread_threshold)
        mask = state.node_mask
        if options.node_mask is not None:
            mask = mask & options.node_mask
        if options.avoid_local_node and \
                0 <= options.local_node_row < mask.shape[0]:
            mask = mask.copy()
            mask[options.local_node_row] = False
        keys = compute_keys(state.totals, state.avail, req, thr, mask)
        cfg = get_config()
        if cfg.scheduler_top_k_fraction > 0:
            node = self._sample_top_k(keys, cfg)
        else:
            node = int(np.argmin(keys))
        if node < 0 or keys[node] == INFEASIBLE_KEY:
            return -1
        available = (keys[node] >> AVAIL_SHIFT) == 0
        if options.require_node_available and not available:
            return -1
        if available:
            state.avail[node] -= np.asarray(req, dtype=np.int32)
        return node

    def _sample_top_k(self, keys: np.ndarray, cfg) -> int:
        feasible = np.flatnonzero(keys != INFEASIBLE_KEY)
        if feasible.size == 0:
            return -1
        k = max(int(cfg.scheduler_top_k_absolute),
                int(np.ceil(cfg.scheduler_top_k_fraction * feasible.size)))
        k = min(k, feasible.size)
        # the k best by packed key (ties broken by row index, like argmin)
        order = feasible[np.argsort(keys[feasible], kind="stable")[:k]]
        return int(self._rng.choice(order))


class SpreadSchedulingPolicy(ISchedulingPolicy):
    """Round-robin over feasible+available nodes (reference
    ``SpreadSchedulingPolicy``: best-effort even spreading with a rotating
    start cursor).

    ``cursor`` is the raylet's one SPREAD cursor: the per-task path
    advances it here, and the raylet's subgroup path hands it to the
    delta beat (``DeltaScheduler.beat``) or its host twin and stores
    the cursor they leave, so both paths rotate from the same place."""

    def __init__(self):
        self._cursor = 0

    @property
    def cursor(self) -> int:
        return self._cursor

    @cursor.setter
    def cursor(self, value: int) -> None:
        self._cursor = int(value)

    def schedule(self, state, req, options):
        thr = threshold_fp(options.spread_threshold)
        mask = state.node_mask if options.node_mask is None \
            else state.node_mask & options.node_mask
        keys = compute_keys(state.totals, state.avail, req, thr, mask)
        n = state.num_nodes
        order = (np.arange(n) + self._cursor) % n
        feasible = keys != INFEASIBLE_KEY
        available = feasible & ((keys >> AVAIL_SHIFT) == 0)
        for pool in (available, feasible):
            cand = order[pool[order]]
            if cand.size:
                node = int(cand[0])
                self._cursor = (node + 1) % n
                if available[node]:
                    state.avail[node] -= np.asarray(req, dtype=np.int32)
                return node
        return -1


class RandomSchedulingPolicy(ISchedulingPolicy):
    """Uniform over feasible+available nodes, pinned threefry stream so runs
    replay deterministically (SURVEY §7 hard part 2)."""

    def __init__(self, seed: int = 0):
        self._rng = np.random.Generator(np.random.Philox(seed))

    def schedule(self, state, req, options):
        thr = threshold_fp(options.spread_threshold)
        mask = state.node_mask if options.node_mask is None \
            else state.node_mask & options.node_mask
        keys = compute_keys(state.totals, state.avail, req, thr, mask)
        available = (keys != INFEASIBLE_KEY) & ((keys >> AVAIL_SHIFT) == 0)
        cand = np.flatnonzero(available)
        if cand.size == 0:
            cand = np.flatnonzero(keys != INFEASIBLE_KEY)
            if cand.size == 0:
                return -1
            return int(self._rng.choice(cand))
        node = int(self._rng.choice(cand))
        state.avail[node] -= np.asarray(req, dtype=np.int32)
        return node


class NodeAffinitySchedulingPolicy(ISchedulingPolicy):
    """Pin to a node; hard affinity fails if the node can't take it, soft
    affinity falls back to hybrid (reference
    ``NodeAffinitySchedulingPolicy``)."""

    def __init__(self):
        self._hybrid = HybridSchedulingPolicy()

    def schedule(self, state, req, options):
        row = options.node_row
        ok = (0 <= row < state.num_nodes) and bool(state.node_mask[row])
        if ok:
            thr = threshold_fp(options.spread_threshold)
            keys = compute_keys(state.totals, state.avail, req, thr,
                                state.node_mask)
            if keys[row] != INFEASIBLE_KEY:
                if (keys[row] >> AVAIL_SHIFT) == 0:
                    state.avail[row] -= np.asarray(req, dtype=np.int32)
                return row
        if options.soft:
            fallback = SchedulingOptions(
                scheduling_type=SchedulingType.HYBRID,
                spread_threshold=options.spread_threshold,
                node_mask=options.node_mask)
            return self._hybrid.schedule(state, req, fallback)
        return -1


class NodeLabelSchedulingPolicy(ISchedulingPolicy):
    """Restrict to nodes matching a label selector (resolved by the
    caller into ``options.node_mask``), hybrid within the match set;
    hard selectors with no matching node park (-1), soft ones fall back
    to the unrestricted hybrid (reference
    ``NodeLabelSchedulingPolicy`` hard/soft label constraints)."""

    def __init__(self):
        self._hybrid = HybridSchedulingPolicy()

    def schedule(self, state, req, options):
        node = self._hybrid.schedule(state, req, options)
        if node >= 0 or not options.soft:
            return node
        fallback = SchedulingOptions(
            scheduling_type=SchedulingType.HYBRID,
            spread_threshold=options.spread_threshold)
        return self._hybrid.schedule(state, req, fallback)


def _bucket(n: int, floor: int = 8) -> int:
    """Smallest power of two >= max(n, floor) — XLA compilation bucketing
    (same discipline as the raylet's device batch path)."""
    n = max(int(n), floor)
    return 1 << (n - 1).bit_length()


class DeltaScheduler:
    """Device-resident delta-scheduling heartbeat engine.

    Keeps three residents in HBM between beats: a mirror of the CRM's
    dense state (totals/avail/placement mask), the interned scheduling
    class request matrix, and a carried (classes x nodes) packed-key
    tensor bit-identical to ``contract.compute_keys`` on the mirror.
    Each ``beat``:

    1. asks the CRM what changed since the last synced epoch
       (``ClusterResourceManager.delta_view``), stages ONLY the dirty
       rows host->HBM through one of two staging slots (double
       buffering: beat N+1's upload enqueues while beat N's readback is
       still in flight — dispatch is async, the host blocks only on the
       consumed counts buffer), and re-scores only the touched key
       columns (``ops.hybrid_kernel.apply_dirty_rows``);
    2. falls back to a full re-upload + ``full_rescore`` when the dirty
       fraction crosses ``scheduler_delta_max_dirty_fraction``, the
       journal was truncated, array shapes grew, or the spread
       threshold changed;
    3. runs the fused water-fill + per-class argmin
       (``ops.hybrid_kernel.fused_beat``) with this beat's ephemeral
       avail overrides (planned-load debits) and soft mask (suspect
       avoidance) — ONE readback per beat, not one per class.  The
       packed buffer carries the water-fill counts AND the
       per-(class, node) lease budgets the kernel priced off its own
       post-beat avail (``contract.compute_budgets`` twin); the lease
       plane reads them via ``last_budgets``/``budget_row_host``
       without any extra device sync.

    Placements are advisory exactly like the snapshot path: the CRM
    stays authoritative, commits happen through ``subtract`` at
    dispatch, which marks the rows dirty for the next beat.  Counts are
    bit-identical to ``schedule_grouped`` on a fresh snapshot — the
    randomized delta-sequence oracle test holds delta path == full
    rescore == CPU oracle.
    """

    # the beat places SPREAD groups (``beat(spread=...)``)
    spread_on_device = True

    def __init__(self, crm):
        self._crm = crm
        self._version = -2          # pre-first-sync sentinel (< any epoch)
        self._thr: int | None = None
        # device residents
        self._totals = None
        self._avail = None
        self._mask = None
        self._keys = None
        self._reqs = None
        self._ones = None           # resident all-true extra mask
        self._n = 0                 # padded node axis
        self._r = 0                 # padded resource axis
        self._cap_c = 0             # padded class axis
        self._n_real = 0
        self._r_real = 0
        # class slot registry (+ host copies to rebuild across resyncs)
        self._slot_of: dict[bytes, int] = {}
        self._class_host: dict[int, np.ndarray] = {}
        self._free_slots: list[int] = []
        self._next_slot = 0
        # double-buffered staging: the previous beat's upload stays
        # referenced until its transfer can no longer be in flight
        self._stage: list = [None, None]
        self._parity = 0
        self._empty_ov = None
        self._last_amin = None
        # beat-emitted lease budgets: host (C_real, n_real) slice of the
        # packed readback, refreshed every beat; seq lets the publisher
        # tell "new beat" from "same beat re-read"
        self._budgets_host: np.ndarray | None = None
        self._budget_seq = 0
        # the last SPREAD beat's (feasible-phase counts, cursor after
        # each group), host slices of its readback
        self._spread_host = None
        # groups: real groups placed; pref_groups/avoid_groups/
        # spread_groups: of those, the groups with a preferred row / an
        # avoid flag / the SPREAD strategy
        self.stats = {"beats": 0, "delta_beats": 0, "full_rescores": 0,
                      "clean_beats": 0, "rows_uploaded": 0,
                      "classes_installed": 0, "groups": 0,
                      "pref_groups": 0, "avoid_groups": 0,
                      "spread_groups": 0}

    # -- public surface -----------------------------------------------------
    def beat(self, group_reqs, group_counts, overrides=None,
             extra_mask=None, require_available: bool = False,
             spread_threshold: float | None = None, prefs=None,
             avoids=None, local_row: int = -1, spread=None,
             cursor: int = 0) -> np.ndarray:
        """Sync the mirror, schedule G classes, return (G, n+1) int32
        counts (column n = infeasible/queued-nowhere), matching
        ``hybrid_kernel.schedule_grouped`` on a fresh CRM snapshot.

        ``overrides``: {row: int32 avail vector} applied for this beat
        only (the raylet's planned-load debits).  ``extra_mask``: host
        bool (n,) soft mask ANDed into the placement mask for this beat
        (suspect avoidance) — the carried key tensor ignores it.

        ``prefs`` (G,) preferred node row per group (-1 = none) and
        ``avoids`` (G,) avoid flags keeping a group off ``local_row``
        (avoid wins over a preferred row): a beat with any of them runs
        the localized program (``schedule_grouped_localized``'s
        semantics), one without runs the plain one.  Groups may share a
        class.

        ``spread`` (G,) bools mark the SPREAD groups: a beat with any
        runs the SPREAD variant, whose marked groups take the
        round-robin fill from ``cursor`` (``SpreadSchedulingPolicy``'s,
        rotating over the CRM's rows) in order among the others.  The
        feasible-phase counts and the cursor after each group ride the
        same readback (``last_spread``); the last is the new cursor.
        """
        with phase("delta.beat"):
            with phase("delta.view"):
                from ..common.config import get_config

                thr = int(threshold_fp(spread_threshold))
                v, totals, avail, place_mask, rows = \
                    self._crm.delta_view(self._version)
                n_real, r_real = totals.shape
                cfg = get_config()
                resync = (rows is None or self._totals is None
                          or thr != self._thr or n_real != self._n_real
                          or r_real != self._r_real)
                if not resync and rows and len(rows) > \
                        cfg.scheduler_delta_max_dirty_fraction * n_real:
                    # the fallback knob: 0.0 disables the delta path
                    resync = True
            with phase("delta.upload"):
                if resync:
                    self._full_sync(totals, avail, place_mask, thr)
                    self.stats["full_rescores"] += 1
                elif rows:
                    self._delta_sync(sorted(rows), totals, avail,
                                     place_mask, thr)
                    self.stats["delta_beats"] += 1
                    self.stats["rows_uploaded"] += len(rows)
                else:
                    self.stats["clean_beats"] += 1
                self._version = v
                self.stats["beats"] += 1

            with phase("delta.stage"):
                group_reqs = np.ascontiguousarray(
                    np.asarray(group_reqs, np.int32))
                g = group_reqs.shape[0]
                if group_reqs.shape[1] != self._r_real:
                    # caller densified at an older width; columns only
                    # ever append, so zero-padding to the mirror's width
                    # is exact
                    norm = np.zeros((g, self._r_real), np.int32)
                    w = min(self._r_real, group_reqs.shape[1])
                    norm[:, :w] = group_reqs[:, :w]
                    group_reqs = norm
                slots = self._ensure_classes(group_reqs, thr)
                gp = _bucket(g)
                slots_p = np.full((gp,), self._cap_c, np.int32)
                slots_p[:g] = slots
                counts_p = np.zeros((gp,), np.int32)
                counts_p[:g] = np.asarray(group_counts, np.int32)

                loc = self._pack_locality(gp, g, prefs, avoids, local_row)
                sp = self._pack_spread(gp, g, spread, cursor, n_real)
                ov = self._pack_overrides(overrides)
                if extra_mask is None:
                    em = self._ones
                else:
                    emp = np.zeros((self._n,), bool)
                    emp[:n_real] = np.asarray(extra_mask, bool)[:n_real]
                    em = self._put_extra_mask(emp)

            with phase("delta.dispatch"):
                counts_d, amin_d = self._fused_call(
                    slots_p, counts_p, em, ov, thr, require_available, loc,
                    sp)
                self._last_amin = amin_d
            with phase("delta.readback"):
                # the one sanctioned host<-device readback of the beat:
                # rows [:gp] are the water-fill counts, rows [gp:] the
                # lease budgets
                packed = np.asarray(counts_d)
                self._spread_host = None
                if sp is not None:
                    # the SPREAD variant's last gp rows
                    tail, packed = packed[-gp:], packed[:-gp]
                    self._spread_host = (tail[:g, :n_real], tail[:g, -1])
                counts = packed[:gp]
                self._budgets_host = packed[gp:, :n_real]
                self._budget_seq += 1
                return np.concatenate(
                    [counts[:g, :n_real], counts[:g, -1:]], axis=1)

    def hit_rate(self) -> float:
        """Fraction of beats served without a full re-upload/rescore."""
        b = self.stats["beats"]
        return 0.0 if not b else 1.0 - self.stats["full_rescores"] / b

    def retire_class(self, req_vec) -> bool:
        """Forget an interned scheduling class, freeing its slot (the
        next new class reuses it and rewrites the key row)."""
        key = np.ascontiguousarray(
            np.asarray(req_vec, np.int32)).tobytes()
        slot = self._slot_of.pop(key, None)
        if slot is None:
            return False
        self._class_host.pop(slot, None)
        self._free_slots.append(slot)
        return True

    def keys_row_host(self, req_vec) -> np.ndarray:
        """Carried key row of one interned class vs the real nodes —
        verification surface for the parity tests (deliberate
        readback)."""
        key = np.ascontiguousarray(
            np.asarray(req_vec, np.int32)).tobytes()
        row = np.asarray(self._keys[self._slot_of[key]])
        return row[:self._n_real].astype(np.int64)

    def peek_argmin(self, req_vec) -> int:
        """Best node row for one class per the carried key tensor (the
        lease-grant preview; deliberate readback)."""
        key = np.ascontiguousarray(
            np.asarray(req_vec, np.int32)).tobytes()
        return int(np.asarray(self._last_amin)[self._slot_of[key]])

    # -- beat-emitted lease budgets (host copies off the fused readback) ----
    @property
    def budget_seq(self) -> int:
        """Monotonic count of beats whose budgets have landed."""
        return self._budget_seq

    def last_budgets(self) -> np.ndarray | None:
        """(C, n_real) int32 budgets from the last beat's readback, row
        index == class slot; None before the first beat.  NOT a device
        sync — this is the host slice the beat already fetched."""
        return self._budgets_host

    def last_spread(self) -> tuple[np.ndarray, np.ndarray] | None:
        """The last beat's SPREAD readback: ((G, n_real) feasible-phase
        counts, (G,) cursor after each group), or None when that beat
        had no SPREAD group.  NOT a device sync."""
        return self._spread_host

    def class_vectors(self) -> dict[int, np.ndarray]:
        """{slot: interned dense request vector} for every resident
        class — the publisher's map from budget rows back to lease
        class keys."""
        return dict(self._class_host)

    def budget_row_host(self, req_vec) -> np.ndarray | None:
        """Beat-emitted lease budget of one interned class vs the real
        nodes, or None if the class isn't resident / no beat has run."""
        if self._budgets_host is None:
            return None
        key = np.ascontiguousarray(
            np.asarray(req_vec, np.int32)).tobytes()
        slot = self._slot_of.get(key)
        if slot is None or slot >= self._budgets_host.shape[0]:
            return None
        return self._budgets_host[slot]

    # -- device-layout hooks (the mesh-sharded engine overrides these) ------
    def _put_extra_mask(self, emp):
        """Device placement of a padded per-beat soft mask."""
        import jax
        return jax.device_put(emp)

    def _fused_call(self, slots_p, counts_p, em, ov, thr,
                    require_available, loc=None, sp=None):
        """The fused schedule->argmin device call (the localized program
        when ``loc`` = (prefs, avoids, local_row) is given, the SPREAD
        variant when ``sp`` = (spread flags, cursor, row count) is);
        returns (counts_device (G, n+1), amin_device (C,))."""
        import jax

        from ..ops import hybrid_kernel as hk
        kw = {}
        if loc is not None:
            prefs, avoids, local_row = loc
            kw = {"pref_rows": jax.device_put(prefs),
                  "avoid": jax.device_put(avoids),
                  "local_row": np.int32(local_row)}
        if sp is not None:
            flags, cursor, n_rows = sp
            kw.update(spread=jax.device_put(flags), cursor=np.int32(cursor),
                      n_rows=np.int32(n_rows))
        return hk.fused_beat(
            self._totals, self._avail, self._mask, self._keys, self._reqs,
            jax.device_put(slots_p), jax.device_put(counts_p), em,
            ov[0], ov[1], thr, require_available=require_available, **kw)

    def _put_state(self, ht, ha, hm):
        """Place the padded mirror arrays (+ the resident all-true
        mask); called by _full_sync after shape bookkeeping."""
        import jax
        self._totals = jax.device_put(ht)
        self._avail = jax.device_put(ha)
        self._mask = jax.device_put(hm)
        self._ones = jax.device_put(np.ones(hm.shape, bool))

    def _put_reqs(self, hr):
        import jax
        self._reqs = jax.device_put(hr)

    def _full_rescore_call(self, thr):
        from ..ops import hybrid_kernel as hk
        return hk.full_rescore(self._totals, self._avail, self._mask,
                               self._reqs, thr)

    def _install_classes(self, idx, vecs, thr):
        """Install freshly interned class rows (host idx/vec buffers)
        into the resident request matrix + key tensor."""
        import jax

        from ..ops import hybrid_kernel as hk
        self._reqs, self._keys = hk.apply_dirty_classes(
            self._totals, self._avail, self._mask, self._keys,
            self._reqs, jax.device_put(idx), jax.device_put(vecs), thr)

    def _node_pad(self, n_real: int) -> int:
        """Padded node-axis length (power-of-2 bucket, floor 64)."""
        return _bucket(n_real, 64)

    # -- sync internals -----------------------------------------------------
    def _full_sync(self, totals, avail, mask, thr):
        n_real, r_real = totals.shape
        n = self._node_pad(n_real)
        r = _bucket(r_real)
        if r_real != self._r_real and self._slot_of:
            # width grew: re-key the registry at the new width (dense
            # vectors only ever append columns, so zero-padding is exact)
            rekeyed = {}
            for slot, vec in list(self._class_host.items()):
                nv = np.zeros((r_real,), np.int32)
                nv[:vec.shape[0]] = vec
                self._class_host[slot] = nv
                rekeyed[nv.tobytes()] = slot
            self._slot_of = rekeyed
        ht = np.zeros((n, r), np.int32)
        ht[:n_real, :r_real] = totals
        ha = np.zeros((n, r), np.int32)
        ha[:n_real, :r_real] = avail
        hm = np.zeros((n,), bool)
        hm[:n_real] = mask
        self._n, self._r = n, r
        self._n_real, self._r_real = n_real, r_real
        self._put_state(ht, ha, hm)
        self._empty_ov = None
        self._rebuild_class_plane(thr, rescore=False)
        self._keys = self._full_rescore_call(thr)
        self._thr = thr

    def _delta_sync(self, rows, totals, avail, mask, thr):
        import jax

        from ..ops import hybrid_kernel as hk
        b = _bucket(len(rows))
        idx = np.full((b,), self._n, np.int32)   # padding idx -> dropped
        idx[:len(rows)] = rows
        rt = np.zeros((b, self._r), np.int32)
        ra = np.zeros((b, self._r), np.int32)
        rm = np.zeros((b,), bool)
        rt[:len(rows), :self._r_real] = totals[rows]
        ra[:len(rows), :self._r_real] = avail[rows]
        rm[:len(rows)] = mask[rows]
        # double-buffered staging: enqueue into the free slot; no host
        # block here — the transfer overlaps the previous beat's compute
        staged = jax.device_put((idx, rt, ra, rm))
        self._stage[self._parity] = staged
        self._parity ^= 1
        self._totals, self._avail, self._mask, self._keys = \
            hk.apply_dirty_rows(self._totals, self._avail, self._mask,
                                self._keys, self._reqs, *staged, thr)

    def _rebuild_class_plane(self, thr, rescore=True):
        cap = _bucket(max(self._next_slot, 1))
        hr = np.zeros((cap, self._r), np.int32)
        for slot, vec in self._class_host.items():
            hr[slot, :vec.shape[0]] = vec
        self._cap_c = cap
        self._put_reqs(hr)
        if rescore:
            self._keys = self._full_rescore_call(thr)

    def _ensure_classes(self, group_reqs, thr) -> np.ndarray:
        slots = np.empty((group_reqs.shape[0],), np.int32)
        fresh: list[tuple[int, np.ndarray]] = []
        for i, vec in enumerate(group_reqs):
            key = vec.tobytes()
            slot = self._slot_of.get(key)
            if slot is None:
                slot = self._free_slots.pop() if self._free_slots \
                    else self._next_slot
                if slot == self._next_slot:
                    self._next_slot += 1
                self._slot_of[key] = slot
                self._class_host[slot] = vec.copy()
                fresh.append((slot, vec))
            slots[i] = slot
        if fresh:
            self.stats["classes_installed"] += len(fresh)
            if max(s for s, _ in fresh) >= self._cap_c:
                self._rebuild_class_plane(thr)   # class axis grew
            else:
                b = _bucket(len(fresh))
                idx = np.full((b,), self._cap_c, np.int32)
                vecs = np.zeros((b, self._r), np.int32)
                for j, (slot, vec) in enumerate(fresh):
                    idx[j] = slot
                    vecs[j, :vec.shape[0]] = vec
                self._install_classes(idx, vecs, thr)
        return slots

    def _pack_locality(self, gp, g, prefs, avoids, local_row):
        """(prefs, avoids, local_row) padded to ``gp`` groups, or None
        when no group has a preferred row or an avoid flag (the plain
        program); counts the beat's groups in ``stats``."""
        self.stats["groups"] += g
        if prefs is None and avoids is None:
            return None
        pref_p = np.full((gp,), -1, np.int32)
        avoid_p = np.zeros((gp,), bool)
        if prefs is not None:
            pref_p[:g] = prefs
        if avoids is not None:
            avoid_p[:g] = avoids
        n_pref = int(((pref_p >= 0) & ~avoid_p).sum())
        n_avoid = int(avoid_p.sum())
        if not n_pref and not n_avoid:
            return None
        self.stats["pref_groups"] += n_pref
        self.stats["avoid_groups"] += n_avoid
        return pref_p, avoid_p, int(local_row)

    def _pack_spread(self, gp, g, spread, cursor, n_rows):
        """(spread flags padded to ``gp`` groups, cursor, row count), or
        None when no group is SPREAD; counts them in ``stats``."""
        if spread is None:
            return None
        flags = np.zeros((gp,), bool)
        flags[:g] = spread
        n_spread = int(flags.sum())
        if not n_spread:
            return None
        self.stats["spread_groups"] += n_spread
        return flags, int(cursor) % n_rows, n_rows

    def _pack_overrides(self, overrides):
        import jax
        if not overrides:
            if self._empty_ov is None:
                idx = np.full((8,), self._n, np.int32)
                av = np.zeros((8, self._r), np.int32)
                self._empty_ov = jax.device_put((idx, av))
            return self._empty_ov
        b = _bucket(len(overrides))
        idx = np.full((b,), self._n, np.int32)
        av = np.zeros((b, self._r), np.int32)
        for j, (row, vec) in enumerate(sorted(overrides.items())):
            idx[j] = row
            av[j, :len(vec)] = np.asarray(vec, np.int32)
        return jax.device_put((idx, av))


class CompositeSchedulingPolicy(ISchedulingPolicy):
    """Dispatch on options.scheduling_type (reference
    ``CompositeSchedulingPolicy``)."""

    def __init__(self, seed: int = 0):
        self._policies = {
            SchedulingType.HYBRID: HybridSchedulingPolicy(),
            SchedulingType.SPREAD: SpreadSchedulingPolicy(),
            SchedulingType.RANDOM: RandomSchedulingPolicy(seed),
            SchedulingType.NODE_AFFINITY: NodeAffinitySchedulingPolicy(),
            SchedulingType.NODE_LABEL: NodeLabelSchedulingPolicy(),
        }

    @property
    def spread(self) -> SpreadSchedulingPolicy:
        """The SPREAD policy, whose ``cursor`` the raylet's subgroup
        path shares."""
        return self._policies[SchedulingType.SPREAD]

    def schedule(self, state, req, options):
        return self._policies[options.scheduling_type].schedule(
            state, req, options)
