"""The one traffic generator: a deployment and a traffic mix, read from
their data files, become a cluster and a raylet; the configuration's
round module (``rounds/<name>.py``) makes the rounds it places.

Everything here is drawn from ``--seed``.  Node totals, class vectors
and, after them, the round module's draws (the backlog, the task
batches) come from one stream (``Deployment.rng``); the CRM churn
between rounds and the choice of rounds compared with the reference
from streams of their own, so a change to one leaves the others alone.

The generator keeps its own record of the cluster (``Deployment.totals``,
``.avail``, ``.ref_vecs``, in the configuration's column order), built
from the drawn totals and usage and booked by every churn op.  The
reference places against that record, never against the program's CRM,
so a fault in how the CRM books churn shows as a placement that differs.
"""

from __future__ import annotations

from collections import deque

import numpy as np

CU_PER_UNIT = 100


def draw_columns(rng, columns: list[dict], n: int) -> np.ndarray:
    """(n, len(columns)) int64 centi-units: column ``c`` is drawn from
    ``[low, high)`` times ``unit_cu``, then set to 0 with probability
    ``zero_share``."""
    out = np.zeros((n, len(columns)), np.int64)
    for j, col in enumerate(columns):
        out[:, j] = rng.integers(col["low"], col["high"], size=n) \
            * col["unit_cu"]
    zero = np.array([col["zero_share"] for col in columns])
    out[rng.random(out.shape) < zero] = 0
    return out


def class_key(names: list[str], cu_row) -> str:
    """The lease class key the budget board files a class under: the
    sorted ``name:cu`` pairs of its non-zero entries, or ``zero``."""
    parts = sorted((n, int(c)) for n, c in zip(names, cu_row) if c)
    return ",".join(f"{n}:{c}" for n, c in parts) or "zero"


class Deployment:
    """The configuration's cluster: a ``cluster_utils.Cluster`` whose CRM
    holds every node, and the head ``Raylet`` (row 0, no workers) whose
    placement round the window drives."""

    def __init__(self, config: dict, seed: int):
        from ray_tpu.cluster_utils import Cluster
        from ray_tpu.common.ids import NodeID
        from ray_tpu.common.resources import NodeResources

        rng = np.random.default_rng([seed, 0])
        self.rng = rng
        cols = config["node_columns"]
        self.node_names = [c["name"] for c in cols]
        n = config["nodes"]
        totals = draw_columns(rng, cols, n)
        used = (totals * rng.random(totals.shape)
                * config["node_used_share_max"]).astype(np.int64)
        self.cluster = cluster = Cluster()
        try:
            for i in range(n):
                units = {k: int(t) / CU_PER_UNIT
                         for k, t in zip(self.node_names, totals[i])}
                free = {k: int(t - u) for k, t, u
                        in zip(self.node_names, totals[i], used[i]) if t}
                if i == 0:
                    head_id = cluster.add_node(resources=units,
                                               num_workers=0)
                    cluster.crm.update_node_available(head_id, free)
                    continue
                nr = NodeResources(units)
                nr.available_cu = free
                cluster.crm.add_node(NodeID.from_random(), nr)
        except BaseException:
            cluster.stop()
            raise
        self.crm = cluster.crm
        self.raylet = cluster.head()
        # the generator's own record: every node alive, none suspect
        self.totals = totals
        self.avail = totals - used

        ccols = config["class_columns"]
        self.class_names = [c["name"] for c in ccols]
        seen: dict[tuple, None] = {}
        while len(seen) < config["classes"]:
            seen.setdefault(tuple(draw_columns(rng, ccols, 1)[0]), None)
        self.class_cu = [list(k) for k in seen]
        self.class_keys = [class_key(self.class_names, cu)
                           for cu in self.class_cu]
        self.class_vecs = np.stack([self.crm.intern_request(self.request(g))
                                    for g in range(len(self.class_cu))])
        self.ref_vecs = np.stack([self.vector(dict(zip(self.class_names, cu)))
                                  for cu in self.class_cu])

    def vector(self, cu: dict) -> np.ndarray:
        """A request of ``{name: centi-units}`` in the record's columns."""
        unknown = set(k for k, c in cu.items() if c) - set(self.node_names)
        if unknown:
            raise ValueError(f"request names no node column: {unknown}")
        return np.array([cu.get(k, 0) for k in self.node_names], np.int64)

    def request(self, g: int):
        """A fresh ``ResourceRequest`` of class ``g``, as a submission
        builds one."""
        from ray_tpu.common.resources import ResourceRequest
        return ResourceRequest({k: c / CU_PER_UNIT for k, c
                                in zip(self.class_names, self.class_cu[g])
                                if c})

    def stop(self) -> None:
        self.cluster.stop()


class Churn:
    """Between rounds: ``ops`` debits or refunds of one request on random
    nodes through the CRM's own ``force_subtract``/``add_back``, so the
    dirty journal the next round syncs from is real.  Each op is booked
    in the deployment's own record too, as those calls state: a debit
    may go below 0, a refund stops at the node's total.

    Refunds go to the oldest debt, as tasks finish in about the order
    they started; so a round's ops touch about ``ops`` distinct rows and
    its dirty-row upload keeps one size.  (``chip_smoke.Churn``, from
    which this is copied, refunds the newest debt, which often undoes a
    debit of the same round and lets the upload's size bucket vary.)"""

    def __init__(self, dep: Deployment, request: dict, ops: int, seed: int):
        from ray_tpu.common.resources import ResourceRequest
        self.dep, self.crm, self.ops = dep, dep.crm, ops
        self.rng = np.random.default_rng([seed, 1])
        self.req = ResourceRequest(request)
        self.vec = dep.vector({k: round(v * CU_PER_UNIT)
                               for k, v in request.items()})
        self.debts: deque[int] = deque()
        self.n = len(dep.totals)

    def step(self, ops: int | None = None, refunds: bool = True) -> None:
        avail, totals = self.dep.avail, self.dep.totals
        for _ in range(self.ops if ops is None else ops):
            if refunds and self.debts and self.rng.random() < 0.5:
                row = self.debts.popleft()
                self.crm.add_back(row, self.req)
                avail[row] = np.minimum(totals[row], avail[row] + self.vec)
            else:
                row = int(self.rng.integers(0, self.n))
                self.crm.force_subtract(row, self.req)
                avail[row] -= self.vec
                self.debts.append(row)
