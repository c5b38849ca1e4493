"""The four-chip sweep's segment program compiled for a described TPU
v5e 2x2 host: the same ``_fleet_sharded`` the harness drives through
``run_fleet(..., mesh=)``, 16 replicas of the tiny cell on each chip.
Nothing runs; the TPU compiler refuses here what it would refuse on the
chip. The topology is described inside a fixture, never at import."""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tiny_cells import tiny_files

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)


def sharded_segment(sim: dict, mix: dict, devices):
    """Compile one segment of the sweep driver's fleet on ``devices``."""
    from chipbench.drivers.sweep import Sweep
    from repro.core.fleet import _fleet_sharded

    drv = Sweep(sim, mix, 5, 1, tempfile.mkdtemp())
    mesh = Mesh(np.array(devices), ("replica",))
    rep, shard = NamedSharding(mesh, P()), NamedSharding(mesh, P("replica"))
    state = jax.eval_shape(lambda s: jax.tree.map(
        lambda a: jnp.broadcast_to(a, (drv.R,) + jnp.shape(a)), s),
        drv.state0)
    keys = jax.eval_shape(lambda k: jax.random.split(k, drv.R),
                          drv.state0.key)

    def sds(tree, sharding):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            jnp.shape(a), a.dtype, sharding=sharding), tree)

    return _fleet_sharded.lower(
        drv.cfg, sds(drv.statics, rep), sds(drv.scns, shard),
        sds(drv.pols, shard), sds(state, shard), sds(keys, shard),
        n_steps=drv.seg, scheduler="fcfs",
        kw_items=(("macro", True), ("summary_only", True)), mesh=mesh,
        axis="replica").compile()


def test_sharded_sweep_segment_compiles_for_v5e_2x2(topo):
    files = tiny_files()
    c = sharded_segment(files["chipbench/configs/tiny.json"]["sim"],
                        files["chipbench/traffic/tiny_sweep.json"],
                        topo.devices[:4])
    m = c.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes + m.generated_code_size_in_bytes)
    assert used < V5E_HBM_BYTES
    # the per-replica program runs no collective: each chip's lockstep
    # loops keep their own trip counts
    text = c.as_text()
    assert "all-reduce" not in text and "all-gather" not in text
