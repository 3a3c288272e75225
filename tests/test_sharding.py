"""Sharding rules: divisibility fallbacks, parameter rules, stacked params."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shd
from repro.launch.mesh import make_host_mesh


@pytest.fixture(scope="module")
def mesh():
    return make_host_mesh(1, 1)


def test_fix_spec_drops_nondividing(mesh):
    # axis size 1 divides everything -> kept as-is
    assert shd.fix_spec_for(mesh, P("data", None), (4, 4)) == P("data", None)
    # unknown axis dropped
    assert shd.fix_spec_for(mesh, P("pod", None), (4, 4)) == P(None, None)


def test_fix_spec_nondivisible_replicates():
    """On a fake 4-way mesh shape, a dim of 6 cannot shard 4 ways."""
    class FakeMesh:
        axis_names = ("model",)
        shape = {"model": 4}
    assert shd._fix_spec(("model",), (6,), FakeMesh()) == (None,)
    assert shd._fix_spec(("model",), (8,), FakeMesh()) == ("model",)


def test_fix_spec_tuple_axes():
    class FakeMesh:
        axis_names = ("pod", "data")
        shape = {"pod": 2, "data": 4}
    assert shd._fix_spec((("pod", "data"),), (16,), FakeMesh()) == (("pod", "data"),)
    # greedy prefix: dim 4 shards over pod (2) and drops data (2*4=8 ∤ 4)
    assert shd._fix_spec((("pod", "data"),), (4,), FakeMesh()) == ("pod",)
    assert shd._fix_spec((("pod", "data"),), (3,), FakeMesh()) == (None,)


def test_fix_spec_pads_short_specs():
    class FakeMesh:
        axis_names = ("data",)
        shape = {"data": 2}
    assert shd._fix_spec(("data",), (4, 8, 8), FakeMesh()) == ("data", None, None)


def test_param_rules_attention(mesh):
    spec = shd.spec_for_param("groups/blocks/pos0/attn/wq", (64, 64), mesh)
    assert spec == P("data", "model")
    spec = shd.spec_for_param("groups/blocks/pos0/attn/wo", (64, 64), mesh)
    assert spec == P("model", "data")


def test_param_rules_stacked_scan_axis(mesh):
    """Stacked (n_repeat, ...) params get leading axes replicated."""
    spec = shd.spec_for_param("groups/blocks/pos0/mlp/w_gate", (8, 64, 128), mesh)
    assert spec == P(None, "data", "model")


def test_param_rules_moe_experts(mesh):
    spec = shd.spec_for_param("moe/we_gate", (16, 64, 128), mesh)
    assert spec == P("model", "data", None)
    spec = shd.spec_for_param("moe/we_down", (16, 128, 64), mesh)
    assert spec == P("model", None, "data")
    # stacked variant
    spec = shd.spec_for_param("groups/blocks/pos1/moe/we_up", (4, 16, 64, 128), mesh)
    assert spec == P(None, "model", "data", None)


def test_param_rules_embeddings(mesh):
    assert shd.spec_for_param("tok/embed", (512, 64), mesh) == P("model", "data")


def test_param_rules_default_replicated(mesh):
    assert shd.spec_for_param("final_norm/scale", (64,), mesh) == P(None)


def test_named_shardings_tree(mesh):
    params = {"attn": {"wq": jax.ShapeDtypeStruct((64, 64), jnp.float32)},
              "norm": {"scale": jax.ShapeDtypeStruct((64,), jnp.float32)}}
    sh = shd.named_shardings(params, mesh)
    assert sh["attn"]["wq"].spec == P("data", "model")
    assert sh["norm"]["scale"].spec == P(None)


class _Mesh2:
    """Fake 1x2 (data x model) mesh for spec-only tests."""
    axis_names = ("data", "model")
    shape = {"data": 1, "model": 2}


def test_model_axis_size_and_heads_divide():
    assert shd.model_axis_size(_Mesh2()) == 2
    assert shd.model_axis_size() == 1          # no ambient mesh
    assert shd.heads_divide(4, _Mesh2())
    assert not shd.heads_divide(3, _Mesh2())   # 3 heads, 2-way axis
    assert not shd.heads_divide(4)             # no ambient mesh


def test_cache_spec_head_axis_layouts():
    m = _Mesh2()
    # paged pool (n_pages, hkv, pt, hd): head axis shards, pages replicate
    assert shd.spec_for_cache("cache/layer0/k", (41, 2, 8, 16), m) \
        == P(None, "model", None, None)
    # stacked dense slab (n_repeat, B, hkv, max_len, hd)
    assert shd.spec_for_cache("groups/blocks/v", (3, 4, 2, 64, 16), m) \
        == P(None, None, "model", None, None)
    # non-dividing head count falls back to replication
    assert shd.spec_for_cache("k", (41, 3, 8, 16), m) \
        == P(None, None, None, None)


def test_cache_spec_state_leaves_replicate():
    m = _Mesh2()
    # MLA latent pages (n_pages, pt, lat): no head axis
    assert shd.spec_for_cache("cache/ckv", (41, 8, 16), m) == P(None, None, None)
    assert shd.spec_for_cache("cache/krope", (41, 8, 8), m) == P(None, None, None)
    # recurrent SSM state
    assert shd.spec_for_cache("cache/ssm", (4, 2, 16, 16), m) \
        == P(None, None, None, None)
    assert shd.spec_for_cache("cache/conv", (4, 2, 4, 16), m) \
        == P(None, None, None, None)


def test_cache_spec_only_matches_exact_leaf(mesh):
    # "wkv_a" ends in neither "k" nor "v" as a path COMPONENT: param rules
    # still apply, cache rules don't
    assert shd.spec_for_cache("attn/wkv_a", (64, 32), _Mesh2()) is None
    assert shd.spec_for_param("attn/wk", (64, 64), mesh) == P("data", "model")
    # and spec_for_param routes real cache leaves through the cache rule
    # instead of replicating them
    assert shd.spec_for_param("cache/k", (41, 2, 8, 16), _Mesh2()) \
        == P(None, "model", None, None)


def test_shard_noop_without_mesh():
    x = jnp.ones((8, 8))
    y = shd.shard(x, "data", None)
    assert (y == x).all()


def test_shard_inside_jit_with_mesh(mesh):
    @jax.jit
    def f(x):
        return shd.shard(x, "data", "model") * 2

    with jax.set_mesh(mesh):
        y = f(jnp.ones((4, 4)))
    assert (y == 2).all()
