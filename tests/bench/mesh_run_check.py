"""The four-chip cell through ``bench/run.py``'s ``main`` at the tiny size,
on four CPU devices; ``test_bench_run.py`` runs it in a child python:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python tests/bench/mesh_run_check.py <empty directory> \\
            [no_exchange | fp8_control | spill]

``no_exchange`` breaks the timed path underneath: each chip keeps its own
share of the MLP's row-parallel down projection, the all-reduce between
the chips left out. ``fp8_control`` judges the fp8 reference's picks in
the program's place, as ``bench/control.py`` does on the chip. ``spill``
gives the pool eight pages, so that sequences spill to the second tier
and come back on the mesh.

The last line of standard output is one JSON object: the run's exit
code, how the served weights and KV pool lie over the devices, and the
spills and restores; the line before it is the run's result.
"""

from __future__ import annotations

import functools
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src"), str(HERE)]

WORKLOAD = "mistral-nemo-12b-tp4.chat-batch"
#: a shard's layer-0 bytes for eight pages of the tiny nemo, one
#: full-length sequence (16 tokens x 2 layers x 4 KV heads x 24 x K and V
#: x 2 bytes, a quarter a shard)
SPILL_LAYER0_BYTES = 8 * 16 * 2 * 4 * 24 * 2 * 2 // 4


def spread(tree) -> dict:
    """Devices the leaves lie on, and the largest share of a bf16 leaf's
    elements (a weight matrix, a table, the KV pages) that one device
    holds: 1 where some such leaf lies whole on every device."""
    import jax
    import numpy as np
    devices, most = set(), 0.0
    for leaf in jax.tree.leaves(tree):
        devices |= leaf.sharding.device_set
        if leaf.dtype == jax.numpy.bfloat16:
            shard = leaf.sharding.shard_shape(leaf.shape)
            most = max(most, float(np.prod(shard) / np.prod(leaf.shape)))
    return {"devices": len(devices), "largest_share": most}


def no_exchange_mlp(p, x):
    """The program's SwiGLU with its down projection summed on each chip
    alone: the partial sums are never exchanged."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import BATCH, MODEL_AXIS, shard
    from repro.models import layers
    h = jax.nn.silu(layers.linear(x, p["w_gate"])) * layers.linear(
        x, p["w_up"])
    h = shard(h, BATCH, None, MODEL_AXIS)
    rest = (None,) * (h.ndim - 1)

    def local(h, w):
        return jnp.dot(h, w.astype(h.dtype),
                       preferred_element_type=jnp.float32).astype(x.dtype)

    return jax.shard_map(local, in_specs=(P(*rest, MODEL_AXIS),
                                          P(MODEL_AXIS, None)),
                         out_specs=P(*rest, None), check_vma=False)(
        h, p["w_down"])


def main(argv) -> int:
    import jax
    import bench.run as run_mod
    from bench import harness
    from bench_helpers import cpu_device, make_tiny_root
    from repro.models import layers

    if argv[1:] == ["no_exchange"]:
        layers.mlp = no_exchange_mlp
    elif argv[1:] == ["fp8_control"]:
        harness.run = functools.partial(harness.run, control=True)
    elif argv[1:] == ["spill"]:
        harness.pool_bytes_beside = lambda params: (SPILL_LAYER0_BYTES,
                                                    4 * SPILL_LAYER0_BYTES)

    assert len(jax.devices()) == 4, jax.devices()
    root = make_tiny_root(Path(argv[0]))
    seen = {}
    build = harness.build

    def keeping(*a, **k):
        served = build(*a, **k)
        serve = served.engine.serve

        def serving(**kw):
            out = serve(**kw)
            seen["pool"] = served.engine._last_pool.state
            seen["spills"] = kw["scheduler"].preemptions
            seen["restores"] = kw["scheduler"].restores
            return out

        served.engine.serve = serving
        seen["params"] = served.params
        seen["geom"] = served.geom
        return served

    harness.build = keeping
    rc = run_mod.main(["--workload", WORKLOAD, "--seed", "3000000019",
                       "--seconds", "2", "--trace", "0"], root=root,
                      check_device=cpu_device, cache_dir=None)
    print(json.dumps({"rc": rc, "weights": spread(seen["params"]),
                      "pool": spread(seen["pool"]),
                      "n_pages": seen["geom"].n_pages,
                      "spills": seen["spills"],
                      "restores": seen["restores"]}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
