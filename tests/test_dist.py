"""repro.dist beyond the seed contract: divisibility is an invariant of the
rule engine (property-tested over random shapes/meshes), every registry config
produces valid specs on both production meshes, the axis-name collectives
match their stacked duals, and micro-batching rejects bad splits loudly."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, list_archs
from repro.core.round import micro_value_and_grad
from repro.dist import collectives
from repro.dist.sharding import (_param_spec, data_axes, mesh_axis_size,
                                 param_specs)
from repro.launch.mesh import SpecMesh, production_spec_mesh
from repro.models import get_model

MESH = production_spec_mesh()
MESH_MP = production_spec_mesh(multi_pod=True)

_NAMES = ["wq", "wk", "wv", "wo", "wi", "tok", "unembed", "router",
          "in_proj", "out_proj", "scale", "bias", "conv_w", "mystery"]
_PARENTS = [(), ("layers",), ("layers", "attn"), ("layers", "moe"),
            ("m", "layers", "mlp"), ("blocks", "r1", "rec")]


def _assert_spec_valid(spec, shape, mesh):
    assert len(spec) <= len(shape)
    for dim, entry in enumerate(spec):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        size = mesh_axis_size(mesh, axes)
        assert shape[dim] % size == 0, \
            f"spec {spec} puts {axes} (size {size}) on dim {dim} of {shape}"


@settings(max_examples=200, deadline=None)
@given(st.sampled_from(_NAMES), st.sampled_from(_PARENTS),
       st.lists(st.sampled_from([1, 2, 3, 5, 8, 12, 16, 20, 64, 96, 2560]),
                min_size=1, max_size=4),
       st.sampled_from([1, 2, 3, 4, 8, 16]),
       st.sampled_from([1, 2, 4, 16, 32]),
       st.booleans())
def test_param_spec_never_violates_divisibility(name, parent, shape,
                                                model_sz, data_sz, fsdp):
    mesh = SpecMesh({"data": data_sz, "model": model_sz})
    fsdp_axes = ("data",) if fsdp else ()
    spec = _param_spec(parent + (name,), tuple(shape), mesh, "model",
                       fsdp_axes)
    _assert_spec_valid(spec, shape, mesh)


@pytest.mark.parametrize("mesh", [MESH, MESH_MP], ids=["16x16", "2x16x16"])
@pytest.mark.parametrize("arch", list_archs())
@pytest.mark.parametrize("fsdp", [False, True], ids=["tp", "fsdp"])
def test_production_configs_yield_valid_specs(arch, mesh, fsdp):
    """Acceptance: full (published-shape) configs, both production meshes."""
    cfg = get_config(arch)
    model = get_model(cfg)
    params = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0)))
    specs = param_specs(params, mesh, fsdp=fsdp)
    leaves = jax.tree.leaves(params)
    spec_leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves) == len(spec_leaves)
    for leaf, spec in zip(leaves, spec_leaves):
        _assert_spec_valid(spec, leaf.shape, mesh)


def test_production_matrices_actually_shard():
    """Divisibility fallbacks must not collapse to all-replicated: on the
    16x16 mesh every >=2D weight matrix of the dense 8b config is sharded."""
    cfg = get_config("granite-8b")
    model = get_model(cfg)
    params = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0)))
    specs = param_specs(params, MESH)
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    spec_leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    for (path, leaf), spec in zip(flat, spec_leaves):
        name = str(path[-1].key)
        if name.startswith("w") and max(leaf.shape) >= 256:
            assert any(e is not None for e in spec), \
                f"{[p.key for p in path]} {leaf.shape} left fully replicated"


def test_data_axes_progressive_fallback():
    # batch divisible by data but not pod*data: shards the data suffix only
    from repro.dist.sharding import batch_spec
    assert batch_spec(MESH_MP, 3, 0, 16) == P("data", None, None)
    assert data_axes(MESH_MP) == ("pod", "data")
    assert mesh_axis_size(MESH_MP, ("pod", "data")) == 32


# ------------------------------------------------------------ collectives --
def test_weighted_client_sum_matches_stacked_einsum():
    C, D = 8, 5
    key = jax.random.PRNGKey(0)
    xs = jax.random.normal(key, (C, D))
    coeff = jnp.linspace(0.1, 1.0, C)
    mapped = jax.vmap(
        lambda x, c: collectives.weighted_client_sum({"w": x}, c,
                                                     axis_name="clients"),
        axis_name="clients")(xs, coeff)["w"]
    dense = jnp.einsum("c,cd->d", coeff, xs)
    np.testing.assert_allclose(np.asarray(mapped[0]), np.asarray(dense),
                               rtol=1e-5)
    # every client sees the same (all-reduced) result
    np.testing.assert_allclose(np.asarray(mapped), np.tile(dense, (C, 1)),
                               rtol=1e-5)


def test_cross_client_delta_matches_aggregation_numerator():
    from repro.core import aggregation
    C = 6
    key = jax.random.PRNGKey(1)
    w_global = {"a": jax.random.normal(key, (4,))}
    w_stack = {"a": jax.random.normal(jax.random.fold_in(key, 1), (C, 4))}
    coeff = jnp.abs(jax.random.normal(jax.random.fold_in(key, 2), (C,)))
    dense = aggregation._weighted_delta_sum(w_stack, w_global, coeff)["a"]
    mapped = jax.vmap(
        lambda wl, c: collectives.cross_client_delta(
            {"a": wl}, w_global, c, axis_name="clients"),
        axis_name="clients")(w_stack["a"], coeff)["a"]
    np.testing.assert_allclose(np.asarray(mapped[0]), np.asarray(dense),
                               rtol=1e-5)


def test_masked_mean_and_count():
    losses = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    alpha = jnp.asarray([1.0, 0.0, 1.0, 0.0])
    mean, count = jax.vmap(
        lambda l, a: (collectives.masked_mean(l, a, axis_name="c"),
                      collectives.participation_count(a, axis_name="c")),
        axis_name="c")(losses, alpha)
    assert float(mean[0]) == pytest.approx(2.0)   # (1+3)/2
    assert float(count[0]) == pytest.approx(2.0)


# ---------------------------------------------------------- micro batching --
def test_micro_value_and_grad_rejects_indivisible_batch():
    loss = lambda p, b, k: (jnp.mean(p * b["x"]), {})
    vg = micro_value_and_grad(loss, num_micro=3)
    with pytest.raises(ValueError, match="not.*divisible by micro_batches=3"):
        jax.jit(vg)(jnp.ones(()), {"x": jnp.ones((4, 2))},
                    jax.random.PRNGKey(0))


def test_micro_value_and_grad_matches_full_batch_when_divisible():
    loss = lambda p, b, k: (jnp.mean((p - b["x"]) ** 2), {})
    p = jnp.float32(0.3)
    batch = {"x": jnp.arange(8, dtype=jnp.float32).reshape(8, 1)}
    key = jax.random.PRNGKey(0)
    (l1, _), g1 = micro_value_and_grad(loss, 1)(p, batch, key)
    (l4, _), g4 = micro_value_and_grad(loss, 4)(p, batch, key)
    np.testing.assert_allclose(float(l1), float(l4), rtol=1e-6)
    np.testing.assert_allclose(float(g1), float(g4), rtol=1e-6)
