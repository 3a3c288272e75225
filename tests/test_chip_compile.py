"""Compile the main path's kernels for a described TPU v5e, without a chip.

The TPU compiler is installed beside JAX, and it compiles for a chip that is
described rather than attached (``jax.experimental.topologies``). That
refuses what interpret mode cannot see: blocks that do not fit the scoped
VMEM limit, slices not aligned to the tiling, kernels GSPMD cannot
partition. Each test asserts that the compile passes and that the compiled
program holds the Pallas kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library, so
describing it at import would make the test workers collect different tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import planner
from repro.kernels import ops
from repro.kernels.matmul3d import matmul3d
from repro.kernels.paged_attention import paged_decode_attention

#: (q heads, kv heads) of qwen2.5-3b and mistral-nemo-12b; head_dim 128
WIDTHS = {"qwen2.5-3b": (16, 2), "mistral-nemo-12b": (32, 8)}
HEAD_DIM = 128


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_routes(monkeypatch):
    """The kernel entries ask ``ops.on_tpu()``, which sees this CPU host;
    steer them to the compiled-kernel route the chip takes."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("arch", sorted(WIDTHS))
@pytest.mark.parametrize("seq", [128, 256, 512, 1024, 2048, 4096])
def test_flash_compiles_at_every_prefill_bucket(one_chip, tpu_routes, arch,
                                                seq):
    hq, hkv = WIDTHS[arch]
    q = _spec(one_chip, (1, hq, seq, HEAD_DIM))
    kv = _spec(one_chip, (1, hkv, seq, HEAD_DIM))
    text = _compiled_text(
        lambda q, k, v: ops.attention(q, k, v, causal=True), q, kv, kv)
    assert "tpu_custom_call" in text


def test_flash_compiles_at_an_unaligned_prompt(one_chip, tpu_routes):
    """A prompt that is no block multiple against a page-aligned cache
    depth, as paged admission prefills it: the entry pads both sequences
    instead of shrinking blocks to divisors the tiling refuses."""
    hq, hkv = WIDTHS["qwen2.5-3b"]
    q = _spec(one_chip, (1, hq, 1999, HEAD_DIM))
    kv = _spec(one_chip, (1, hkv, 4128, HEAD_DIM))
    text = _compiled_text(
        lambda q, k, v: ops.attention(q, k, v, causal=True), q, kv, kv)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("slots,p_max,n_pages", [(32, 256, 5824),
                                                 (4, 258, 2065)])
def test_page_walk_compiles_at_the_served_shape(one_chip, tpu_routes, slots,
                                                p_max, n_pages):
    """qwen2.5-3b widths, page_tokens 16: the benchmark's 32 slots of 256
    pages over its 5824-page pool, and 4 slots of a 4128-token depth (258
    pages, no whole number of walk blocks) over 2065 pages."""
    hq, hkv = WIDTHS["qwen2.5-3b"]
    pt = 16
    q = _spec(one_chip, (slots, hq, 1, HEAD_DIM))
    pages = _spec(one_chip, (n_pages, hkv, pt, HEAD_DIM))
    tables = _spec(one_chip, (slots, p_max), jnp.int32)
    lens = _spec(one_chip, (slots,), jnp.int32)
    text = _compiled_text(paged_decode_attention, q, pages, pages, tables,
                          lens)
    assert "tpu_custom_call" in text
    assert "all-gather" not in text


def test_matmul3d_compiles_at_a_kernel_bench_shape(one_chip):
    m = k = n = 2048
    plan = planner.matmul_kernel_plan(m, k, n, in_bytes=2)
    text = _compiled_text(lambda a, b: matmul3d(a, b, plan=plan),
                          _spec(one_chip, (m, k)), _spec(one_chip, (k, n)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kernel", ["flash", "page walk"])
def test_kernels_compile_split_over_a_2x2_mesh(topo, tpu_routes, kernel):
    """mistral-nemo-12b widths on a data x model 2x2 mesh of the described
    chips: the kernels run per shard (``over_heads``), so the program holds
    the Pallas kernel and gathers nothing."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    at = lambda *spec: NamedSharding(mesh, P(*spec))
    hq, hkv = WIDTHS["mistral-nemo-12b"]
    slots = 4
    if kernel == "flash":
        fn = lambda q, k, v: ops.attention(q, k, v, causal=True)
        args = (_spec(at("data", "model"), (slots, hq, 2048, HEAD_DIM)),
                *[_spec(at("data", "model"), (slots, hkv, 2048, HEAD_DIM))] * 2)
    else:
        fn = paged_decode_attention
        pages = _spec(at(None, "model"), (1025, hkv, 16, HEAD_DIM))
        args = (_spec(at("data", "model"), (slots, hq, 1, HEAD_DIM)),
                pages, pages, _spec(at("data"), (slots, 128), jnp.int32),
                _spec(at("data"), (slots,), jnp.int32))
    with jax.set_mesh(mesh):
        text = _compiled_text(fn, *args)
    assert "tpu_custom_call" in text
    assert "all-gather" not in text


#: ``bytes_limit`` a TPU v5e reports (``memory_stats()``, chip_smoke's
#: memory line)
V5E_BYTES_LIMIT = 16909336064


def test_serving_steps_fit_beside_the_weights_at_the_pool_budget(one_chip,
                                                                tpu_routes):
    """qwen2.5-3b at full width and depth, its pool tiers sized as
    ``pool_bytes_beside`` sizes them on a v5e. Compiled for the chip, the
    paged admission and pooled decode chunk alias the donated pool to their
    output, and each step's peak, with the spill tier beside it, fits in the
    device's ``bytes_limit``. Undonated, or with more pool copies than
    ``POOL_STEP_COPIES`` charges, the decode chunk does not fit."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serve.engine import Engine, EngineConfig, serving_params
    from repro.serve.pool import POOL_FRACTION, POOL_STEP_COPIES
    from repro.serve.scheduler import Scheduler, derive_page_geometry

    cfg = get_config("qwen2.5-3b")
    model = build_model(cfg)
    place = lambda tree: jax.tree.map(
        lambda x: _spec(one_chip, x.shape, x.dtype), tree)
    nbytes = lambda tree: sum(x.size * x.dtype.itemsize
                              for x in jax.tree.leaves(tree))
    params = place(jax.eval_shape(lambda k: serving_params(model.init(k)),
                                  jax.random.PRNGKey(0)))
    # pool_bytes_beside's split (layer 1 = half of layer 0 on a TPU target)
    free = V5E_BYTES_LIMIT - nbytes(params)
    layer0 = int(POOL_FRACTION * free / (POOL_STEP_COPIES + 0.5))
    max_len = 4128
    geom = derive_page_geometry(cfg, max_len, page_tokens=16,
                                max_slots=10**6, layer0_bytes=layer0,
                                layer1_bytes=layer0 // 2)
    engine = Engine(model, params, EngineConfig(max_len=max_len))
    engine.pools._build = lambda make: place(jax.eval_shape(make))
    pool, spill = engine.init_paged_pool(Scheduler(n_slots=4, pages=geom))
    i32 = lambda *shape: _spec(one_chip, shape, jnp.int32)
    steps = {
        "admit": engine.prefill_role._make_paged_admit_fn(geom).lower(
            params, i32(1, 4096), i32(), i32(), i32(),
            i32(geom.max_pages_per_slot), pool),
        "decode": engine.decode_role.pool_chunk(8).lower(params, pool),
    }
    for name, lowered in steps.items():
        mem = lowered.compile().memory_analysis()
        assert mem.alias_size_in_bytes >= nbytes(pool), name
        peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        assert peak + nbytes(spill) <= V5E_BYTES_LIMIT, (name, peak)
