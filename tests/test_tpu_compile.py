"""Compile the main-path Pallas kernel for a described TPU v5e, no chip needed.

The fused fleet round step (`kernels.fleet_step`) is compiled at 1e7
clients for the fleet and serve step programs, with and without histogram
telemetry, on one described v5e chip and sharded over a described 4-chip
mesh.  The TPU compiler refuses here what the chip would refuse: blocks that
break the (8, 128) tiling rule, or a tile that overflows the kernel's scoped
VMEM.  Nothing runs, so these tests say nothing about results or times.

The topology is described inside a module fixture (never at import time):
only one process may load the TPU library, so under several pytest workers
only the worker given this file loads it.  This is the only test file that
describes the chip.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core.scheduling import Policy
from repro.energy import battery as battery_lib, step_ops
from repro.energy.costs import DecodeCostModel
from repro.kernels import fleet_step
from repro.serve import admission
from repro.serve.fleet_serve import TrainLoad
from repro.serve.qos import QoSSpec

N = 10_000_000
BAT = battery_lib.BatteryConfig(capacity=2.5, leak=0.25, init_charge=0.5)
QOS = QoSSpec(prompt_tokens=64.0, full_decode_tokens=128.0,
              short_decode_tokens=32.0)
DECODE = DecodeCostModel(2.0 ** -8, 2.0 ** -9, 2.0 ** -6)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    from repro.launch.mesh import make_mesh
    return make_mesh((4,), ("data",), devices=topo.devices[:4])


@pytest.fixture(autouse=True, scope="module")
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _program(kind: str, hist: bool):
    if kind == "fleet":
        return step_ops.fleet_step_program(BAT, Policy.SUSTAINABLE, hist=hist)
    train = TrainLoad.create(np.full(N, 4), 0.25, policy=Policy.GREEDY)
    return step_ops.serve_step_program(
        BAT, DECODE, QOS, admission.BatteryGated(hi=1.0, lo=1.0), train,
        hist=hist)


def _env_shapes(program, bound: dict, sharding_of) -> dict:
    """Shape-only kernel env: the bound leaves keep their shapes; every other
    input (charge, harvest, want, requests, admit, valid, ...) is a
    per-client float32 buffer, except the admission scale."""
    env = {}
    for nm in fleet_step._env_names(program, None):
        if nm in bound:
            shape, dtype = np.shape(bound[nm]), jnp.asarray(bound[nm]).dtype
        elif nm == "admit":
            shape, dtype = (), jnp.float32
        else:
            shape, dtype = (N,), jnp.float32
        env[nm] = jax.ShapeDtypeStruct(shape, dtype,
                                       sharding=sharding_of(shape))
    return env


@pytest.mark.parametrize("hist", [False, True])
@pytest.mark.parametrize("kind", ["fleet", "serve"])
def test_fused_step_compiles_for_v5e(kind, hist, one_chip):
    program, bound = _program(kind, hist)
    env = _env_shapes(program, bound, lambda shape: one_chip)
    compiled = jax.jit(lambda e: fleet_step.fused_step(
        program, e, n=N, emit=True, interpret=False)).lower(env).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # the fleet's per-client buffers stay in HBM; the kernel adds no
    # fleet-sized temporaries beyond its padded outputs
    assert mem.temp_size_in_bytes < 16 * 2 ** 30


@pytest.mark.parametrize("kind", ["fleet", "serve"])
def test_fused_step_sharded_compiles_for_v5e_mesh(kind, four_chips):
    program, bound = _program(kind, False)
    env = _env_shapes(
        program, bound,
        lambda shape: NamedSharding(four_chips,
                                    P("data") if shape == (N,) else P()))
    compiled = jax.jit(lambda e: fleet_step.fused_step_sharded(
        program, e, n=N, mesh=four_chips, emit=True, interpret=False)
    ).lower(env).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text         # the psum of the stat partials
