"""Logical-axis rule resolution: dedup, divisibility, missing axes.
AbstractMesh lets us test the production 16x16 / 2x16x16 resolution logic
without 512 real devices."""
import pytest
from jax.sharding import AbstractMesh, AxisType, PartitionSpec as P

from repro.sharding import DEFAULT_RULES, logical_to_pspec


@pytest.fixture(scope="module")
def pod():
    return AbstractMesh((16, 16), ("data", "model"),
                        axis_types=(AxisType.Auto,) * 2)


@pytest.fixture(scope="module")
def multipod():
    return AbstractMesh((2, 16, 16), ("pod", "data", "model"),
                        axis_types=(AxisType.Auto,) * 3)


def test_basic_mapping(pod):
    assert logical_to_pspec(("embed", "mlp"), DEFAULT_RULES, pod) \
        == P("data", "model")


def test_missing_mesh_axis_dropped(pod, multipod):
    # "batch" maps to ("pod", "data"): single-pod drops "pod"
    assert logical_to_pspec(("batch", "seq"), DEFAULT_RULES, pod) == P("data", None)
    assert logical_to_pspec(("batch", "seq"), DEFAULT_RULES, multipod) \
        == P(("pod", "data"), None)


def test_duplicate_axis_first_wins(pod):
    assert logical_to_pspec(("mlp", "mlp"), DEFAULT_RULES, pod) == P("model", None)


def test_divisibility_guard(pod):
    # 4 kv-heads cannot shard over the 16-way model axis
    assert logical_to_pspec(("kv_heads",), DEFAULT_RULES, pod, shape=(4,)) == P(None)
    # 64 can
    assert logical_to_pspec(("kv_heads",), DEFAULT_RULES, pod, shape=(64,)) \
        == P("model")


def test_divisibility_guard_partial(multipod):
    # batch=2 shards over pod(2) but not data(16): greedy prefix
    assert logical_to_pspec(("batch",), DEFAULT_RULES, multipod, shape=(2,)) \
        == P("pod")
    # batch=1 (long_500k) stays replicated
    assert logical_to_pspec(("batch",), DEFAULT_RULES, multipod, shape=(1,)) \
        == P(None)


def test_unknown_logical_axis_is_replicated(pod):
    assert logical_to_pspec(("nonexistent_axis",), DEFAULT_RULES, pod) == P(None)


def test_abstract_params_shapes():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.models import abstract_params, model_params_def
    cfg = get_config("yi-34b")
    abs_tree = abstract_params(model_params_def(cfg), jnp.bfloat16)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(abs_tree))
    assert n > 30e9  # full yi-34b declared without allocating anything
