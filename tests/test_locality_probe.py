"""The raylet round's locality probe (``Raylet._locality_rows``), done as
one batched read of the object store (``MemoryStore.plasma_sizes``), one
of the object directory (``ObjectDirectory.location_rows``) and a numpy
reduction, pinned to the per-argument loop it replaces.

The reference below is that loop as it stood: for each ``ObjectRef``
argument a ``plasma_info`` and a ``directory.locations`` call, bytes
summed per row, the row with most bytes taken, lowest row on ties.
"""

import numpy as np
import pytest

from ray_tpu.common.config import Config, get_config
from ray_tpu.common.ids import JobID, ObjectID, TaskID
from ray_tpu.common.resources import ResourceRequest
from ray_tpu.common.task_spec import (DEFAULT_STRATEGY, SchedulingStrategy,
                                      SchedulingStrategyKind, TaskSpec,
                                      TaskType)
from ray_tpu.native import Arena
from ray_tpu.runtime.object_directory import LOOKUP_SLICE, ObjectDirectory
from ray_tpu.runtime.object_ref import ObjectRef
from ray_tpu.runtime.object_store import (PLASMA_KINDS, MemoryStore,
                                          ObjectLostError, ShmEntry,
                                          SpillEntry)
from ray_tpu.runtime.raylet import _AVOID
from ray_tpu.runtime.serialization import serialize

CAP = 1 << 20           # a 1 MiB arena: a larger payload spills to disk
THRESHOLD = 1024        # payloads above this route to the arena
N_ROWS = 8


@pytest.fixture
def store(tmp_path):
    arena = Arena(str(tmp_path / "arena"), CAP, create=True)
    s = MemoryStore(arena=arena, spill_dir=str(tmp_path / "spill"),
                    direct_call_threshold=THRESHOLD, spill_threshold=0.8)
    yield s
    arena.close()


@pytest.fixture
def raylet(store):
    """The head raylet of a small live cluster (no workers), reading the
    fixture's store, where spilled entries are cheap to make."""
    from ray_tpu.cluster_utils import Cluster
    Config.reset({"health_check_period_ms": 3_600_000})
    c = Cluster()
    try:
        for _ in range(N_ROWS):
            c.add_node(resources={"CPU": 4}, num_workers=0)
        r = c.head()
        cluster_store, r.store = r.store, store
        try:
            yield r
        finally:
            r.store = cluster_store
    finally:
        c.stop()


# -- objects ---------------------------------------------------------------

def _shm(store, directory, rows, n=10_000):
    oid = ObjectID.from_random()
    store.put_serialized(oid, serialize(b"s" * n))
    assert isinstance(store._objects[oid.binary()], ShmEntry)
    for r in rows:
        directory.add_location(oid, r)
    return oid


def _spill(store, directory, rows):
    oid = ObjectID.from_random()
    store.put_serialized(oid, serialize(b"d" * (CAP + 1)))   # past the arena
    assert isinstance(store._objects[oid.binary()], SpillEntry)
    for r in rows:
        directory.add_location(oid, r)
    return oid


def _remote(store, directory, rows, size):
    oid = ObjectID.from_random()
    store.put_remote(oid, size)
    for r in rows:
        directory.add_location(oid, r)
    return oid


def _inband(store):
    oid = ObjectID.from_random()
    store.put(oid, 7)
    return oid


def _spec(args=(), strategy=DEFAULT_STRATEGY):
    job = JobID.from_int(1)
    return TaskSpec(task_id=TaskID.for_task(job), job_id=job,
                    task_type=TaskType.NORMAL_TASK,
                    function_descriptor="probe:task", args=tuple(args),
                    resources=ResourceRequest({"CPU": 1}),
                    strategy=strategy)


def _refs(*oids):
    return [ObjectRef(o) for o in oids]


# -- cases: (raylet, rng) -> (specs, avoid set) ------------------------------

def _several_locations(raylet, rng):
    st, d = raylet.store, raylet.cluster.directory
    oids = [_remote(st, d, rng.choice(N_ROWS, int(rng.integers(1, 5)),
                                      replace=False).tolist(),
                    int(rng.integers(1, 1 << 30)))
            for _ in range(24)]
    return [_spec(_refs(*rng.choice(oids, int(rng.integers(1, 7)))))
            for _ in range(64)], set()


def _repeated_ref(raylet, rng):
    # a on row 1 given twice outweighs b on row 2, which outweighs one a
    st, d = raylet.store, raylet.cluster.directory
    a = _remote(st, d, [1], 10_000)
    b = _remote(st, d, [2], 15_000)
    ra, rb = ObjectRef(a), ObjectRef(b)
    return [_spec([ra, ra, rb]), _spec([rb, ra]), _spec([ra, rb, ra, ra]),
            _spec([ra, ra])], set()


def _inband_absent_other(raylet, rng):
    st, d = raylet.store, raylet.cluster.directory
    inb, absent = _inband(st), ObjectID.from_random()
    p = _remote(st, d, [6], 500)
    return [_spec(_refs(inb, absent) + [3, "x", p]),
            _spec(_refs(absent, inb, p, inb)),
            _spec([b"bytes", 1.5, ObjectRef(inb)]),
            _spec(_refs(absent))], set()


def _entry_kinds(raylet, rng):
    st, d = raylet.store, raylet.cluster.directory
    shm = _shm(st, d, [3, 4])
    spill = _spill(st, d, [5])
    remote = _remote(st, d, [6, 3], 40_000)
    kinds = [shm, spill, remote]
    return [_spec(_refs(*rng.choice(kinds, int(rng.integers(1, 5)))))
            for _ in range(32)] + [_spec(_refs(shm)), _spec(_refs(spill)),
                                   _spec(_refs(remote))], set()


def _byte_ties(raylet, rng):
    # equal bytes on two rows: the lower row wins, whatever the order
    st, d = raylet.store, raylet.cluster.directory
    a = _remote(st, d, [5], 1 << 20)
    b = _remote(st, d, [3], 1 << 20)
    c = _remote(st, d, [6], 100)
    e = _remote(st, d, [2], 60)
    f = _remote(st, d, [2], 40)
    both = _remote(st, d, [7, 4], 9)
    return [_spec(_refs(a, b)), _spec(_refs(b, a)), _spec(_refs(c, e, f)),
            _spec(_refs(f, c, e)), _spec(_refs(both)),
            _spec(_refs(both, a, b))], set()


def _zero_bytes(raylet, rng):
    # a plasma entry of size 0 still names its rows, as the loop did
    st, d = raylet.store, raylet.cluster.directory
    z = _remote(st, d, [7, 4], 0)
    return [_spec(_refs(z)), _spec(_refs(z, z))], set()


def _knob_off(raylet, rng):
    get_config().locality_aware_scheduling = False
    return _several_locations(raylet, rng)


def _avoid_flag(raylet, rng):
    specs, _ = _several_locations(raylet, rng)
    specs.append(_spec())   # no args: only its flag puts it in the probe
    return specs, {s.task_id for s in specs[::3] + specs[-1:]}


def _no_plasma_bytes(raylet, rng):
    # args but no bytes on any row: inband, absent, no location, plain
    st, d = raylet.store, raylet.cluster.directory
    nowhere = _remote(st, d, [], 1234)
    return [_spec([1, 2]), _spec(_refs(_inband(st))),
            _spec(_refs(ObjectID.from_random())), _spec(_refs(nowhere)),
            _spec(_refs(nowhere) + ["y"])], set()


def _strategies(raylet, rng):
    # only DEFAULT-strategy specs are probed
    specs, _ = _several_locations(raylet, rng)
    spread = SchedulingStrategy(kind=SchedulingStrategyKind.SPREAD)
    specs[5] = _spec(specs[5].args, strategy=spread)
    specs[9] = _spec(specs[9].args, strategy=SchedulingStrategy())
    return specs, set()


def _past_one_slice(raylet, rng):
    # more ids than one hold of either lock takes
    st, d = raylet.store, raylet.cluster.directory
    rows = rng.choice(N_ROWS, 16, replace=True).tolist()
    n = LOOKUP_SLICE // 16 + 40
    specs = [_spec(_refs(*[_remote(st, d, [rows[j]],
                                   int(rng.integers(1, 1 << 27)))
                           for j in range(16)]))
             for _ in range(n)]
    return specs + [_spec() for _ in range(8)], set()


def _reference_row(raylet, spec):
    """The per-argument loop the batched probe replaces."""
    if not spec.args or not get_config().locality_aware_scheduling:
        return None
    by_row: dict[int, int] = {}
    for a in spec.args:
        if isinstance(a, ObjectRef):
            kind, size = raylet.store.plasma_info(a.id)
            if kind in PLASMA_KINDS:
                for r in raylet.cluster.directory.locations(a.id):
                    by_row[r] = by_row.get(r, 0) + size
    if not by_row:
        return None
    return min(by_row, key=lambda r: (-by_row[r], r))


def _reference_rows(raylet, specs, avoid):
    """({id(spec): row | _AVOID}, plasma args looked up) by the loop."""
    default = SchedulingStrategyKind.DEFAULT
    locs, looked_up = {}, 0
    on = get_config().locality_aware_scheduling
    for s in specs:
        if s.task_id in avoid:
            locs[id(s)] = _AVOID
        elif s.args and s.strategy.kind is default:
            row = _reference_row(raylet, s)
            locs[id(s)] = -1 if row is None else row
            if on:
                looked_up += sum(
                    1 for a in s.args if isinstance(a, ObjectRef)
                    and raylet.store.plasma_info(a.id)[0] in PLASMA_KINDS)
    return locs, looked_up


@pytest.mark.parametrize("make", [
    _several_locations, _repeated_ref, _inband_absent_other, _entry_kinds,
    _byte_ties, _zero_bytes, _knob_off, _avoid_flag, _no_plasma_bytes,
    _strategies, _past_one_slice], ids=lambda f: f.__name__.strip("_"))
def test_batched_probe_matches_per_argument_loop(raylet, make):
    rng = np.random.default_rng(11)
    specs, avoid = make(raylet, rng)
    with raylet._cv:
        raylet._avoid_local.clear()
        raylet._avoid_local.update(avoid)
    want, looked_up = _reference_rows(raylet, specs, avoid)

    before = raylet.queue_stats()["locality_args"]
    got = raylet._locality_rows(specs)
    assert raylet.queue_stats()["locality_args"] - before == looked_up
    assert got == want
    assert all(type(v) is int for v in got.values())
    assert raylet._avoid_local == avoid        # read, not consumed

    # the per-task CPU path takes the batch of one: the same rule
    for s in specs:
        if got.get(id(s), _AVOID) != _AVOID:
            row = raylet._locality_row(s)
            assert (-1 if row is None else row) == got[id(s)]

    if make is _repeated_ref:
        assert [got[id(s)] for s in specs] == [1, 2, 1, 1]
    elif make is _byte_ties:
        assert [got[id(s)] for s in specs] == [3, 3, 2, 2, 4, 3]
    elif make is _zero_bytes:
        assert [got[id(s)] for s in specs] == [4, 4]
    elif make in (_knob_off, _no_plasma_bytes):
        assert set(got.values()) == {-1}
    elif make is _avoid_flag:
        assert list(got.values()).count(_AVOID) == len(avoid)
    elif make is _past_one_slice:
        assert looked_up > LOOKUP_SLICE


def test_locality_args_counts_a_round(raylet):
    """A locality round counts its plasma args once, next to
    ``group_probed``; a round with no args counts none."""
    rng = np.random.default_rng(3)
    specs, _ = _several_locations(raylet, rng)
    plain = [_spec() for _ in range(16)]
    stats = raylet.queue_stats()
    raylet._schedule_rows_soft([_record(s) for s in plain])
    assert raylet.queue_stats()["locality_args"] == stats["locality_args"]
    raylet._schedule_rows_soft([_record(s) for s in specs])
    after = raylet.queue_stats()
    assert after["locality_args"] - stats["locality_args"] == sum(
        len(s.args) for s in specs)
    assert after["group_probed"] - stats["group_probed"] == len(specs)


def test_mixed_round_probes_once_and_places_as_per_task(raylet):
    """A mixed-strategy round runs the batched probe once and places
    every task as the per-task loop does, ``_options_for`` probing each
    spec itself (the batch of one)."""
    rng = np.random.default_rng(5)
    specs, _ = _several_locations(raylet, rng)
    # a stateless non-DEFAULT strategy, so both passes place it alike
    pinned = SchedulingStrategy(kind=SchedulingStrategyKind.NODE_AFFINITY,
                                node_id=raylet.node_id, soft=True)
    specs[3] = _spec(specs[3].args, strategy=pinned)
    avoid = {specs[1].task_id, specs[8].task_id}

    def flag():
        with raylet._cv:
            raylet._avoid_local.clear()
            raylet._avoid_local.update(avoid)

    flag()
    stats = raylet.queue_stats()
    got = raylet._schedule_rows_soft([_record(s) for s in specs])
    after = raylet.queue_stats()
    assert not raylet._avoid_local              # flags consumed
    assert after["group_probed"] == stats["group_probed"]
    assert after["locality_args"] - stats["locality_args"] == sum(
        len(s.args) for t, s in enumerate(specs)
        if t != 3 and s.task_id not in avoid)

    flag()
    snapshot = raylet._effective_snapshot()
    n = snapshot.node_mask.shape[0]
    by_class: dict = {}
    for t, s in enumerate(specs):
        by_class.setdefault(s.scheduling_class(), []).append(t)
    want = [-1] * len(specs)
    for idxs in by_class.values():
        req = specs[idxs[0]].resources.dense(raylet.crm.resource_index,
                                             snapshot.totals.shape[1])
        for t in idxs:
            want[t] = raylet._policy.schedule(
                snapshot, req, raylet._options_for(specs[t], n))
    assert got == want
    assert any(r >= 0 for r in got)


def _record(spec):
    from ray_tpu.runtime.task_manager import TaskRecord
    return TaskRecord(spec, 0, [])


# -- the two batched reads -------------------------------------------------

def _objects(store, directory):
    """Ids of every kind, some on several rows; more than one slice."""
    ids = [_shm(store, directory, [0, 3]), _shm(store, directory, []),
           _spill(store, directory, [2]), _inband(store),
           ObjectID.from_random(), _remote(store, directory, [1, 2, 3], 0)]
    ids += [_remote(store, directory, [i % 5, 4 + i % 3], 100 + i)
            for i in range(LOOKUP_SLICE + 30)]
    return ids + ids[:6]        # ids given twice read twice


def _fresh(store, directory, ids):
    pass


def _dropped(store, directory, ids):
    directory.drop(ids[::4])


def _node_removed(store, directory, ids):
    directory.on_node_removed(3)
    directory.on_node_removed(2)


def _poisoned(store, directory, ids):
    for oid in ids[:3] + ids[10:20]:
        store.poison(oid, ObjectLostError("lost"))


@pytest.mark.parametrize("change", [_fresh, _dropped, _node_removed,
                                    _poisoned],
                         ids=lambda f: f.__name__.strip("_"))
def test_batched_reads_match_single_reads(store, change):
    directory = ObjectDirectory()
    ids = _objects(store, directory)
    change(store, directory, ids)

    keys = [oid.binary() for oid in ids]
    sizes = store.plasma_sizes(keys)
    assert sizes.dtype == np.int64 and sizes.shape == (len(ids),)
    for oid, size in zip(ids, sizes.tolist()):
        kind, want = store.plasma_info(oid)
        assert size == (want if kind in PLASMA_KINDS else -1)

    counts, rows = directory.location_rows(keys)
    assert counts.dtype == rows.dtype == np.int64
    assert counts.shape == (len(ids),) and rows.shape == (counts.sum(),)
    starts = np.concatenate([[0], np.cumsum(counts)])
    for i, oid in enumerate(ids):
        assert tuple(sorted(rows[starts[i]:starts[i + 1]].tolist())) == \
            directory.locations(oid)

    if change is _poisoned:
        assert (sizes[:3] == -1).all() and (sizes[10:20] == -1).all()
    if change is _node_removed:
        assert not np.isin(rows, [2, 3]).any()
    assert store.plasma_sizes([]).shape == (0,)
    assert [a.shape for a in directory.location_rows([])] == [(0,), (0,)]
