"""Fast unit tests for the dist.sharding logical-axis DSL.

These cover the pure mapping logic (spec / batch_spec / disabled /
constrain no-op paths) without spawning the 8-device subprocess suite
in test_dist_exec.py — the sharding layer stays covered in the
non-slow CI lane.
"""
import collections

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import Rules, batch_placement, constrain


class FakeMesh:
    """Stands in for jax.sharding.Mesh where only .shape is consulted."""

    def __init__(self, **axes):
        self.shape = collections.OrderedDict(axes)


RULES = Rules(data=("data",), model="model", tp="model", seq=None)


# ---------------------------------------------------------------------------
# disabled rules
# ---------------------------------------------------------------------------

def test_disabled_rules_replicate_everything():
    r = Rules.disabled()
    assert not r.enabled
    assert r.spec("data", "model") == P(None, None)
    assert r.batch_spec(8, FakeMesh(data=4)) == P()
    x = jnp.ones((2, 3))
    assert constrain(x, r, "batch", None) is x


def test_enabled_flag():
    assert RULES.enabled
    assert Rules(data=("data",)).enabled
    assert Rules(model="model").enabled
    assert not Rules().enabled


# ---------------------------------------------------------------------------
# spec: weight placement
# ---------------------------------------------------------------------------

def test_spec_maps_logical_names():
    assert RULES.spec("data", "model") == P(("data",), "model")
    assert RULES.spec("model", "data") == P("model", ("data",))
    assert RULES.spec(None, "tp") == P(None, "model")
    assert RULES.spec(None, None, None) == P(None, None, None)


def test_spec_multi_axis_data():
    r = Rules(data=("pod", "data"), model="model", tp="model")
    assert r.spec("data", "model") == P(("pod", "data"), "model")


def test_spec_fsdp_off_makes_weights_resident():
    r = Rules(data=("data",), model="model", tp="model", fsdp=False)
    assert r.spec("data", "model") == P(None, "model")
    assert r.spec("model", "data") == P("model", None)


def test_spec_rejects_unknown_logical_axis():
    with pytest.raises(ValueError):
        RULES.spec("bogus")


# ---------------------------------------------------------------------------
# batch_spec: graceful degradation
# ---------------------------------------------------------------------------

def test_batch_spec_divisible():
    assert RULES.batch_spec(4, FakeMesh(data=2, model=4)) == P(("data",))


def test_batch_spec_no_mesh():
    assert RULES.batch_spec(4, None) == P()


def test_batch_spec_non_divisible_batch_unsharded():
    # batch 3 on data=2: cannot shard evenly -> replicate
    assert RULES.batch_spec(3, FakeMesh(data=2, model=4)) == P()


def test_batch_spec_drops_size_one_axes():
    assert RULES.batch_spec(4, FakeMesh(data=1, model=4)) == P()


def test_batch_spec_batch_axes_override_drops_from_right():
    # ZeRO-3 regime: batch rides (data, model); a batch covering only
    # the data axis drops the model axis instead of failing
    r = Rules(data=("data",), model="model",
              batch_axes=("data", "model"), tp=None)
    assert r.batch_spec(8, FakeMesh(data=2, model=4)) == P(("data", "model"))
    assert r.batch_spec(2, FakeMesh(data=2, model=4)) == P(("data",))
    assert r.batch_spec(1, FakeMesh(data=2, model=4)) == P()


def test_batch_spec_indexing_contract():
    # callers take the batch axes as a tuple from batch_placement; the
    # spec names the same axes (jax may normalise ("data",) to "data")
    mesh = FakeMesh(data=2, model=4)
    axes = batch_placement(RULES, mesh, 4)
    assert axes == ("data",)
    lead = RULES.batch_spec(4, mesh)
    assert len(lead) == 1 and lead == P(axes)
    assert batch_placement(RULES, mesh, 3) == ()
    assert batch_placement(RULES, None, 4) == ()


# ---------------------------------------------------------------------------
# constrain: no-op paths
# ---------------------------------------------------------------------------

def test_constrain_without_mesh_is_identity():
    x = jnp.arange(8.0).reshape(2, 4)
    assert constrain(x, RULES, "batch", "tp") is x


def test_constrain_disabled_inside_mesh_is_identity():
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    x = jnp.arange(8.0).reshape(2, 4)
    with jax.set_mesh(mesh):
        assert constrain(x, Rules.disabled(), "batch", None) is x


def test_constrain_under_trivial_mesh_preserves_values():
    # single-device mesh: every axis has size 1, so the constraint
    # must resolve to full replication and values must be untouched
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    x = jnp.arange(12.0).reshape(2, 6)
    with jax.set_mesh(mesh):
        y = jax.jit(lambda t: constrain(t, RULES, "batch", "tp"))(x)
    assert jnp.array_equal(x, y)


def test_constrain_ignores_extra_logical_names():
    x = jnp.ones((2, 3))
    assert constrain(x, RULES, "batch", None, None, None) is x
