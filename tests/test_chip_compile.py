"""The main path's kernels compile for a TPU v5e at real widths.

Nothing runs: the TPU compiler, installed here, compiles for a ``v5e:2x2``
that is described, not attached.  That catches what interpret mode and
the CPU backend cannot (tiling, VMEM limits, partitioning) at no chip
time.  The topology is described inside a fixture, never at import: one
process at a time may load the TPU library, and every xdist worker
imports this file.  Keep these tests in this one file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

N, R, C, B = 1024, 8, 64, 32        # nodes, resource columns, classes, rows


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — no TPU compiler here
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_schedule_grouped(one_chip):
    from ray_tpu.ops.hybrid_kernel import schedule_grouped
    s = lambda *a, **k: _shape(one_chip, *a, **k)      # noqa: E731
    compiled = schedule_grouped.lower(
        s((N, R)), s((N, R)), s((N,), bool), s((C, R)), s((C,)),
        s((C, N), bool), s(())).compile()
    assert compiled.memory_analysis() is not None


def test_fused_beat(one_chip):
    from ray_tpu.ops.hybrid_kernel import fused_beat
    s = lambda *a, **k: _shape(one_chip, *a, **k)      # noqa: E731
    fused_beat.lower(
        s((N, R)), s((N, R)), s((N,), bool), s((C, N)), s((C, R)),
        s((C,)), s((C,)), s((N,), bool), s((8,)), s((8, R)),
        s(())).compile()


@pytest.mark.parametrize("localized", [False, True])
def test_fused_beat_spread_variant(one_chip, localized):
    from ray_tpu.ops.hybrid_kernel import fused_beat
    s = lambda *a, **k: _shape(one_chip, *a, **k)      # noqa: E731
    kw = {"spread": s((C,), bool), "cursor": s(()), "n_rows": s(())}
    if localized:
        kw.update(pref_rows=s((C,)), avoid=s((C,), bool), local_row=s(()))
    fused_beat.lower(
        s((N, R)), s((N, R)), s((N,), bool), s((C, N)), s((C, R)),
        s((C,)), s((C,)), s((N,), bool), s((8,)), s((8, R)),
        s(()), **kw).compile()


def test_apply_dirty_rows(one_chip):
    from ray_tpu.ops.hybrid_kernel import apply_dirty_rows
    s = lambda *a, **k: _shape(one_chip, *a, **k)      # noqa: E731
    apply_dirty_rows.lower(
        s((N, R)), s((N, R)), s((N,), bool), s((C, N)), s((C, R)),
        s((B,)), s((B, R)), s((B, R)), s((B,), bool), s(())).compile()


def test_flash_attention_is_a_tpu_kernel(one_chip):
    from ray_tpu.ops.flash_attention import flash_attention
    q = _shape(one_chip, (1, 2048, 8, 128), jnp.bfloat16)
    compiled = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, interpret=False)).lower(q, q, q).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("grid", [(1, 4), (2, 2)],
                         ids=["flat", "two_level"])
def test_sharded_fused_beat(topo, grid):
    from ray_tpu.ops.shard_reduce import ShardPlane
    mesh = Mesh(np.array(topo.devices[:4]).reshape(grid), ("dcn", "ici"))
    plane = ShardPlane(mesh)
    rows, vec = plane.sh_rows, plane.sh_vec
    cols, repl = plane.sh_cols, plane.sh_repl
    compiled = plane.fused_program().lower(
        _shape(rows, (N, R)), _shape(rows, (N, R)),
        _shape(vec, (N,), bool), _shape(cols, (C, N)),
        _shape(repl, (C, R)), _shape(repl, (C,)), _shape(repl, (C,)),
        _shape(vec, (N,), bool), _shape(vec, (4 * 8,)),
        _shape(rows, (4 * 8, R)), _shape(repl, ())).compile()
    text = compiled.as_text()
    assert "all-reduce" in text          # the water-fill's psum / pmin
    assert "all-gather" in text          # counts + budgets replicated
