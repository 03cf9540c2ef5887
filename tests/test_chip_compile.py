"""The main path compiles for a TPU v5e chip, with no chip attached.

XLA's TPU compiler is installed with jaxlib and compiles for a described
``v5e:2x2`` topology: it refuses what the chip would refuse (tiles not
aligned to (8, 128), kernels that overflow VMEM), which interpret mode
accepts.  Nothing runs here, so these tests say nothing about results
or speed.  The topology is described inside a fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.  Keep every such compile in this one file.
"""
import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import batched as B
from repro.core.bucketing import pad_events
from repro.kernels import policy_score as ps
from repro.serve import PlacementService, ServeConfig
from repro.workload.alibaba import TraceConfig, generate


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A v5e chip sharding, with JAX's persistent compilation cache off:
    a TPU executable written there cannot be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def paper_events():
    """The paper's fleet and stream (1,213 hosts, 8,063 VMs)."""
    cluster, vms = generate(TraceConfig(scale=1.0, seed=0))
    return B.build_events(vms, cluster)


def _specs(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=sharding), tree)


@pytest.mark.parametrize("num_gpus", [2048, 12800])
@pytest.mark.parametrize("kernel", ["mcc", "ecc"])
def test_engine_kernel_compiles_for_v5e(one_chip, kernel, num_gpus):
    """12,800 GPUs is 100 rows of 128 lanes: no multiple of 8 divides
    it, so the kernels must take the whole array as one tile."""
    assert ps.kernel_fits(num_gpus)
    free = jax.ShapeDtypeStruct((num_gpus,), jnp.int32, sharding=one_chip)
    prof = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    if kernel == "mcc":
        fn = jax.jit(lambda f, p: ps.engine_mcc_scores(f, p))
        args = (free, prof)
    else:
        row = jax.ShapeDtypeStruct((1, ps.LANES), jnp.float32,
                                   sharding=one_chip)
        fn = jax.jit(lambda f, p, r: ps.engine_ecc_scores(f, p, r))
        args = (free, prof, row)
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("policy,backend", [("GRMU", "tables"),
                                            ("MECC", "pallas")])
def test_replay_scan_compiles_for_v5e(one_chip, paper_events, policy,
                                      backend):
    pv = pad_events(paper_events)
    st = B.replay_statics(pv, getattr(B, policy), score_backend=backend)
    assert st.score_backend == backend
    fn = jax.jit(functools.partial(B._scan_fn, st), donate_argnums=(0,))
    compiled = fn.lower(
        _specs(B.init_state(pv, st), one_chip),
        _specs(B.trace_arrays(pv), one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == (backend != "tables")


def test_served_decision_step_compiles_for_v5e(one_chip, paper_events):
    svc = PlacementService.for_trace(
        paper_events, ServeConfig(policy="GRMU", micro_batch=64))
    st = svc._statics["GRMU"]
    E = svc._batch_rows
    ev = dict(kind=np.zeros(E, np.uint8), vm_index=np.zeros(E, np.int32),
              profile=np.zeros(E, np.int16), time=np.zeros(E, np.float32),
              idx=np.zeros(E, np.int32))
    B.make_decision_step(st).lower(
        _specs(svc._state, one_chip), _specs(ev, one_chip),
        _specs(svc._rest, one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((E,), jnp.int32, sharding=one_chip),
    ).compile()
