from .broadcast_kernel import plan_fanout, plan_fanout_np, plan_fanout_oracle
from .bundle_kernel import schedule_bundle_groups, schedule_bundle_groups_np
from .flash_attention import flash_attention
from .hybrid_kernel import schedule_grouped, schedule_grouped_np
from .pull_kernel import (choose_sources, choose_sources_np,
                          choose_sources_oracle)
from .ring_attention import (full_attention, ring_attention,
                             ulysses_attention)
from .shard_reduce import build_mesh, plane_for, resolve_shards

__all__ = ["schedule_bundle_groups", "schedule_bundle_groups_np",
           "schedule_grouped", "schedule_grouped_np",
           "choose_sources", "choose_sources_np", "choose_sources_oracle",
           "plan_fanout", "plan_fanout_np", "plan_fanout_oracle",
           "flash_attention", "full_attention", "ring_attention",
           "ulysses_attention",
           "build_mesh", "plane_for", "resolve_shards"]
