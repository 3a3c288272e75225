"""Subprocess helper for the sharded-kernel cells (NOT a pytest file).

Run by tests/test_kernels.py as a child python with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the flag must be set
before jax imports; tests/conftest.py forbids it in the pytest process).

    python tests/over_heads_check.py 2x2     # data x model mesh sizes

Runs the Pallas flash kernel (``ops.attention``) and the Pallas page walk
(``paged_decode_attention``), in interpret mode, under the mesh and
without one, and asserts that:

  * the sharded results equal the unsharded ones;
  * each result comes back split over the slot batch on `data` and over the
    heads on `model` (``repro.distributed.sharding.over_heads``);
  * the compiled mesh programs hold no all-gather: every shard computes only
    its own slots and heads, none gathers the others' inputs.

Prints OVER_HEADS_OK on success.
"""

import os
import sys

if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    sys.exit("run with XLA_FLAGS=--xla_force_host_platform_device_count=4")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import tiling
from repro.distributed.sharding import fix_spec_for
from repro.kernels import ops
from repro.kernels.paged_attention import paged_decode_attention
from repro.launch.mesh import make_host_mesh

B, HQ, HKV, D = 4, 8, 4, 64
SEQ = 256
N_PAGES, PT, P_MAX = 33, 16, 8
PLAN = tiling.AttentionPlan(block_q=128, block_kv=128)


def flash(q, k, v):
    return ops.attention(q, k, v, causal=True, plan=PLAN, impl="pallas")


def walk(q, k_pages, v_pages, tables, lens):
    return paged_decode_attention(q, k_pages, v_pages, tables, lens,
                                  impl="pallas")


def placed(mesh, x, spec):
    return jax.device_put(x, NamedSharding(mesh, fix_spec_for(
        mesh, P(*spec), x.shape)))


def check(name, fn, args, specs, mesh):
    want = np.asarray(jax.jit(fn)(*args))
    with jax.set_mesh(mesh):
        args_m = [placed(mesh, a, s) for a, s in zip(args, specs)]
        compiled = jax.jit(fn).lower(*args_m).compile()
        got = compiled(*args_m)
    hlo = compiled.as_text()
    assert "all-gather" not in hlo, f"{name}: the mesh program gathers"
    split = fix_spec_for(mesh, P("data", "model"), got.shape)
    assert got.sharding.is_equivalent_to(NamedSharding(mesh, split),
                                         got.ndim), (name, got.sharding)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)
    print(f"{name}: sharded {got.sharding.spec} equals unsharded")


def main(spec: str) -> None:
    data, model = (int(s) for s in spec.split("x"))
    mesh = make_host_mesh(data=data, model=model)
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    q = jax.random.normal(keys[0], (B, HQ, SEQ, D), jnp.float32)
    k = jax.random.normal(keys[1], (B, HKV, SEQ, D), jnp.float32)
    v = jax.random.normal(keys[2], (B, HKV, SEQ, D), jnp.float32)
    bh = ("data", "model")
    check("flash", flash, (q, k, v), (bh, bh, bh), mesh)

    q1 = jax.random.normal(keys[3], (B, HQ, 1, D), jnp.float32)
    pages = (N_PAGES, HKV, PT, D)
    kp = jax.random.normal(keys[4], pages, jnp.float32)
    vp = jax.random.normal(keys[5], pages, jnp.float32)
    rng = np.random.default_rng(0)
    tables = jnp.asarray(np.stack([rng.choice(np.arange(1, N_PAGES), P_MAX,
                                              replace=False)
                                   for _ in range(B)]), jnp.int32)
    lens = jnp.asarray(rng.integers(1, P_MAX * PT, size=B), jnp.int32)
    h = (None, "model")
    check("page walk", walk, (q1, kp, vp, tables, lens),
          (bh, h, h, ("data",), ("data",)), mesh)
    print("OVER_HEADS_OK")


if __name__ == "__main__":
    main(sys.argv[1])
