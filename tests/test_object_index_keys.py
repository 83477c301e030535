"""The object store's and the object directory's indexes are keyed by the
id's raw bytes (``ObjectID.binary()``), not by the ``ObjectID``.

Their public methods still take and hand back ``ObjectID``s.  Every check
below asks with a *fresh* id equal to the one the object was sealed or
located under (a pickle round-trip, as a deserialized task argument is),
so a lookup that only hits for the very key object would fail here.
"""

import pickle
from types import SimpleNamespace

import numpy as np
import pytest

from ray_tpu.common.ids import BaseID, JobID, ObjectID, TaskID
from ray_tpu.common.resources import ResourceRequest
from ray_tpu.common.task_spec import DEFAULT_STRATEGY, TaskSpec, TaskType
from ray_tpu.native import Arena
from ray_tpu.runtime.object_directory import ObjectDirectory
from ray_tpu.runtime.object_ref import ObjectRef
from ray_tpu.runtime.object_store import MemoryStore, ShmEntry, SpillEntry
from ray_tpu.runtime.raylet import Raylet
from ray_tpu.runtime.serialization import serialize

CAP = 1 << 20           # a 1 MiB arena; spilling starts past 80 % of it
THRESHOLD = 1024        # payloads above this route to the arena
BIG = 300_000           # three of these pass the spill threshold


@pytest.fixture
def store(tmp_path):
    arena = Arena(str(tmp_path / "arena"), CAP, create=True)
    s = MemoryStore(arena=arena, spill_dir=str(tmp_path / "spill"),
                    direct_call_threshold=THRESHOLD, spill_threshold=0.8)
    yield s
    arena.close()


def _fresh(oid: ObjectID) -> ObjectID:
    """An equal id that is not the same object (nor holds the same bytes
    object), as a deserialized argument is."""
    copy = pickle.loads(pickle.dumps(oid))
    assert copy == oid and copy is not oid
    return copy


def _ids(n):
    return [ObjectID.from_random() for _ in range(n)]


# -- cases: (store, directory) -> None ----------------------------------------

def _store_reads(store, directory):
    remote, inband, shm, absent = _ids(4)
    store.put_remote(remote, 500)
    store.put(inband, {"v": 7})
    store.put_serialized(shm, serialize(b"s" * 10_000))
    assert store.plasma_info(_fresh(remote)) == ("remote", 500)
    assert store.plasma_info(_fresh(inband)) == ("inband", 0)
    assert store.plasma_info(_fresh(shm))[0] == "shm"
    assert store.plasma_info(_fresh(absent)) == (None, 0)
    keys = [_fresh(o).binary() for o in (remote, inband, shm, absent)]
    assert store.plasma_sizes(keys).tolist() == \
        [500, -1, store.plasma_info(shm)[1], -1]
    assert store.get([_fresh(inband)]) == [{"v": 7}]
    assert store.get([_fresh(shm)], timeout=1) == [b"s" * 10_000]
    assert store.peek(_fresh(inband)) == {"v": 7}
    assert all(store.contains(_fresh(o)) for o in (remote, inband, shm))
    assert not store.contains(_fresh(absent))
    ready, not_ready = store.wait([_fresh(absent), _fresh(inband)], 1,
                                  timeout=0)
    assert ready == [inband] and not_ready == [absent]
    # a second seal under an equal id loses, as for the same id
    store.put(_fresh(inband), "later")
    assert store.peek(inband) == {"v": 7}
    assert store.size() == 3


def _directory_reads(store, directory):
    a, b, nowhere = _ids(3)
    for r in (3, 1):
        directory.add_location(a, r)
    directory.add_location(_fresh(a), 1)       # the same copy again
    directory.add_location(b, 5)
    assert directory.locations(_fresh(a)) == (1, 3)
    assert directory.locations(_fresh(nowhere)) == ()
    assert directory.has_location(_fresh(b), 5)
    assert directory.is_tracked(_fresh(a))
    assert not directory.is_tracked(_fresh(nowhere))
    counts, rows = directory.location_rows(
        [_fresh(o).binary() for o in (a, nowhere, b)])
    assert counts.tolist() == [2, 0, 1]
    assert sorted(rows[:2].tolist()) == [1, 3] and rows[2:].tolist() == [5]
    mask = directory.location_matrix([_fresh(a), _fresh(b)], 4)
    assert mask.tolist() == [[False, True, False, True], [False] * 4]
    assert directory.stats() == {"num_tracked": 2, "num_copies": 3}


def _drop(store, directory):
    ids = _ids(5)
    for i, oid in enumerate(ids):
        store.put_remote(oid, 100 + i)
        directory.add_location(oid, i % 2)
    store.put_serialized(ids[0], serialize(b"x" * 10_000))   # real bytes
    store.delete([_fresh(o) for o in ids])
    directory.drop([_fresh(o) for o in ids])
    assert store.size() == 0 and store.stats()["arena_bytes_in_use"] == 0
    assert directory.stats() == {"num_tracked": 0, "num_copies": 0}


def _node_removed(store, directory):
    ids = _ids(6)
    for i, oid in enumerate(ids):
        directory.add_location(oid, 2)
        if i % 3 == 0:
            directory.add_location(oid, 4)
    lost = directory.on_node_removed(2)
    assert lost == [ids[i] for i in (1, 2, 4, 5)]         # insertion order
    assert all(type(o) is ObjectID for o in lost)
    assert directory.on_node_removed(4) == [ids[0], ids[3]]
    assert directory.stats() == {"num_tracked": 0, "num_copies": 0}


def _sole_copies(store, directory):
    ids = _ids(5)
    for i, oid in enumerate(ids):
        directory.add_location(_fresh(oid), 7)
        if i == 2:
            directory.add_location(oid, 1)
    sole = directory.sole_copies_on(7)
    assert sole == [ids[i] for i in (0, 1, 3, 4)]         # insertion order
    assert all(type(o) is ObjectID for o in sole)
    assert directory.sole_copies_on(1) == []


def _spill_walk(store, directory):
    a, b, c, d = _ids(4)
    store.put_serialized(a, serialize(b"a" * BIG))
    store.put_serialized(b, serialize(b"b" * BIG))
    store.put(d, "small")
    assert store.get([_fresh(a)]) == [b"a" * BIG]       # a is touched last
    store.put_serialized(c, serialize(b"c" * BIG))      # spills b, the LRU
    listed = store.list_objects()
    assert [(o, kind) for o, _, kind in listed] == \
        [(b, "spilled"), (d, "in_band"), (a, "shm"), (c, "shm")]
    assert all(type(o) is ObjectID for o, _, _ in listed)
    spilled = store._objects[b.binary()]
    assert isinstance(spilled, SpillEntry)
    assert spilled.path.endswith(b.hex())
    assert store.get([_fresh(b)]) == [b"b" * BIG]       # restored
    assert isinstance(store._objects[b.binary()], (ShmEntry, bytes))


def _listeners(store, directory):
    a, b = _ids(2)
    seen = []
    store.on_ready(_fresh(a), seen.append)
    store.on_ready(_fresh(b), seen.append)
    store.cancel_on_ready(_fresh(b), seen.append)
    store.put(a, 1)
    store.put_remote(b, 10)
    assert seen == [a] and type(seen[0]) is ObjectID
    store.on_ready(_fresh(b), seen.append)               # already there
    assert seen == [a, b]


def _zombie_unpin(store, directory):
    oid = ObjectID.from_random()
    store.put_serialized(oid, serialize(b"z" * 10_000))
    desc = store.descriptor_of(_fresh(oid))              # pins the block
    assert desc[0] == "s"
    store.delete([_fresh(oid)])                          # parks a zombie
    assert store.stats()["arena_bytes_in_use"] > 0
    store.unpin([(_fresh(oid), desc[1])])
    assert store.stats()["arena_bytes_in_use"] == 0
    store.put_serialized(oid, serialize(b"z" * 10_000))
    store.descriptor_of(oid)
    store.delete([oid])
    store.unpin([_fresh(oid)])                           # id-only unpin
    assert store.stats()["arena_bytes_in_use"] == 0


@pytest.mark.parametrize("case", [
    _store_reads, _directory_reads, _drop, _node_removed, _sole_copies,
    _spill_walk, _listeners, _zombie_unpin],
    ids=lambda f: f.__name__.strip("_"))
def test_indexes_answer_fresh_equal_ids(store, case):
    case(store, ObjectDirectory())


# -- the locality probe's lookups ----------------------------------------------

def _spec(args):
    job = JobID.from_int(1)
    return TaskSpec(task_id=TaskID.for_task(job), job_id=job,
                    task_type=TaskType.NORMAL_TASK,
                    function_descriptor="probe:task", args=tuple(args),
                    resources=ResourceRequest({"CPU": 1}),
                    strategy=DEFAULT_STRATEGY)


def test_locality_batch_hashes_no_object_id(store, monkeypatch):
    """The probe's batched reads go by raw keys: ``ObjectID.__hash__``
    is not called once for a 2-spec x 16-arg batch."""
    directory = ObjectDirectory()
    specs = []
    for s in range(2):
        ids = _ids(16)
        for j, oid in enumerate(ids):
            store.put_remote(oid, 1000 * (j + 1))
            directory.add_location(oid, s * 4 + j % 3)
        specs.append(_spec(ObjectRef(_fresh(o)) for o in ids))
    raylet = SimpleNamespace(store=store,
                             cluster=SimpleNamespace(directory=directory))
    calls = []

    def counting_hash(self):
        calls.append(self)
        return BaseID.__hash__(self)

    monkeypatch.setattr(ObjectID, "__hash__", counting_hash)
    rows, looked_up = Raylet._locality_batch(raylet, specs)
    assert calls == []
    hash(ObjectID.from_random())          # the counter does engage
    assert len(calls) == 1
    # per spec its rows s*4 + (0, 1, 2) hold 51, 40 and 45 kB
    assert rows.tolist() == [0, 4] and looked_up == 32
    assert isinstance(rows, np.ndarray)
