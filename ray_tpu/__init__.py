"""ray_tpu — a TPU-native distributed task/actor framework.

A ground-up rebuild of the capabilities of the reference
(``pschafhalter/ray``, a fork of ``ray-project/ray``): dynamic task graph +
actor runtime, two-level scheduling, placement groups, a shared-memory
object store (native C++ arena, zero-copy worker reads, descriptor pinning,
LRU spill/restore), an inter-node object plane (directory + pull manager
with a device-evaluated bandwidth cost model), owner-side reference
counting with lineage reconstruction, an autoscaler runtime loop,
health-check failure detection, runtime environments, a GCS KV store +
pubsub, collectives (XLA device-mesh + KV-rendezvous process groups), an
RPC control plane with a head daemon / client mode / job submission /
CLI / worker-node agents joining over RPC (``start --address=<head>``),
a C++ client frontend over a cross-language gateway (``cpp/``,
``cross_language.export``), observability (metrics endpoint, dashboard HTTP server, structured
logs, Chrome-trace timeline), and the library family (``data``, ``train``, ``tune``,
``serve``, ``rllib``, ``workflow``) — with the scheduling/packing data
planes evaluated as dense TPU computations (JAX/XLA/Pallas) per
BASELINE.json's north star.  Remaining gaps are tracked in ROADMAP.md.

Public API mirrors the reference's (``ray.init/remote/get/put/wait/...``,
SURVEY.md §1 layer 9).
"""

__version__ = "0.1.0"

from .common import (Config, NodeResources, ResourceRequest, get_config)

# The runtime API (init/remote/get/put/...) is imported lazily to keep
# `import ray_tpu` light for scheduler-only users (e.g. the bench harness).
_API_NAMES = ("init", "shutdown", "is_initialized", "remote", "get", "put",
              "wait", "cancel", "kill", "get_actor",
              "available_resources", "cluster_resources", "nodes",
              "drain_node",
              "timeline", "worker_stacks", "get_runtime_context",
              "list_named_actors")


def __getattr__(name):
    if name in _API_NAMES:
        from . import api
        return getattr(api, name)
    if name in ("util", "experimental", "cross_language", "data", "train",
                "tune", "serve", "workflow", "rllib"):
        # NOT `from . import util`: that re-enters __getattr__ via the
        # fromlist hasattr probe before the submodule import finishes.
        # Only submodules that EXIST belong here — forwarding a missing
        # name would turn hasattr()'s AttributeError contract into a
        # ModuleNotFoundError escape.
        import importlib
        return importlib.import_module(f".{name}", __name__)
    raise AttributeError(f"module 'ray_tpu' has no attribute {name!r}")


__all__ = ["Config", "get_config", "NodeResources", "ResourceRequest",
           *_API_NAMES]
