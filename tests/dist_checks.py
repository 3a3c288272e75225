"""Multi-device checks, executed in a subprocess with 8 forced host devices
(tests/test_distributed.py drives this). Exits non-zero on any failure.

Bundled into one process because each subprocess pays jax-import + compile
startup; each check prints PASS so the parent can assert on coverage.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed import sharding as shd


def check_mesh_device_count():
    assert len(jax.devices()) == 8, jax.devices()
    print("PASS mesh_device_count")


def check_moe_ep_matches_dense():
    """Production EP shard_map path == dense GShard path (same routing)."""
    from repro.configs import get_reduced
    from repro.models import moe as moe_mod

    cfg = get_reduced("qwen3-moe-30b-a3b")
    key = jax.random.PRNGKey(0)
    p = moe_mod.init_moe(cfg, key)
    x = jax.random.normal(jax.random.fold_in(key, 1), (4, 16, cfg.d_model),
                          jnp.float32) * 0.1

    y_dense, aux_dense = moe_mod.moe_block(p, x, cfg=cfg, impl="dense")

    mesh = shd.make_mesh((2, 4), ("data", "model"))
    with jax.set_mesh(mesh):
        y_ep, aux_ep = jax.jit(
            lambda p, x: moe_mod.moe_block(p, x, cfg=cfg, impl="ep"))(p, x)
    np.testing.assert_allclose(np.asarray(y_ep, np.float32),
                               np.asarray(y_dense, np.float32),
                               rtol=3e-2, atol=3e-2)
    np.testing.assert_allclose(float(aux_ep), float(aux_dense), rtol=1e-3)
    print("PASS moe_ep_matches_dense")


def check_moe_ep_capacity_drops():
    """With capacity_factor<<1 the EP path drops tokens (zero contribution)
    instead of crashing — GShard semantics."""
    import dataclasses
    from repro.configs import get_reduced
    from repro.models import moe as moe_mod

    cfg = dataclasses.replace(get_reduced("qwen3-moe-30b-a3b"),
                              capacity_factor=0.25)
    key = jax.random.PRNGKey(0)
    p = moe_mod.init_moe(cfg, key)
    x = jax.random.normal(jax.random.fold_in(key, 1), (4, 16, cfg.d_model)) * 0.1
    mesh = shd.make_mesh((2, 4), ("data", "model"))
    with jax.set_mesh(mesh):
        y, _ = jax.jit(lambda p, x: moe_mod.moe_block(p, x, cfg=cfg,
                                                      impl="ep"))(p, x)
    assert bool(jnp.isfinite(y).all())
    print("PASS moe_ep_capacity_drops")


def check_moe_partial_k_matches_dense():
    """Decode-sized batches take the token-gathering partial-K path; it must
    agree with the dense oracle exactly like the weight-gather path."""
    from repro.configs import get_reduced
    from repro.models import moe as moe_mod

    cfg = get_reduced("qwen3-moe-30b-a3b")
    key = jax.random.PRNGKey(3)
    p = moe_mod.init_moe(cfg, key)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 1, cfg.d_model),
                          jnp.float32) * 0.1
    y_dense, aux_dense = moe_mod.moe_block(p, x, cfg=cfg, impl="dense")
    mesh = shd.make_mesh((2, 4), ("data", "model"))
    with jax.set_mesh(mesh):
        y_ep, aux_ep = jax.jit(
            lambda p, x: moe_mod.moe_block(p, x, cfg=cfg, impl="ep"))(p, x)
    np.testing.assert_allclose(np.asarray(y_ep, np.float32),
                               np.asarray(y_dense, np.float32),
                               rtol=3e-2, atol=3e-2)
    np.testing.assert_allclose(float(aux_ep), float(aux_dense), rtol=1e-3)
    print("PASS moe_partial_k_matches_dense")


def check_compressed_psum():
    """int8+EF gradient sync: mean error bounded by quant step; error
    feedback replays the residual next round."""
    from repro.distributed import collectives

    mesh = shd.make_mesh((8,), ("pod",), explicit=True)
    g_local = np.random.default_rng(0).standard_normal((8, 64)).astype(np.float32)
    err0 = np.zeros((8, 64), np.float32)

    def body(g, e):
        return collectives.compressed_psum_mean(g, e, "pod", 8)

    out, new_err = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("pod"), P("pod")),
        out_specs=(P("pod"), P("pod"))))(jnp.asarray(g_local), jnp.asarray(err0))
    true_mean = g_local.mean(axis=0)
    got = np.asarray(out)[0]
    scale = np.abs(g_local).max() / 127.0
    assert np.abs(got - true_mean).max() <= scale * 1.01, \
        (np.abs(got - true_mean).max(), scale)
    # error feedback: residual equals what quantization dropped locally
    assert np.abs(np.asarray(new_err)).max() <= scale * 0.51
    # over repeated rounds with the SAME gradient, the time-average of the
    # compressed means converges to the true mean (unbiased over time)
    e = jnp.asarray(err0)
    acc = np.zeros_like(true_mean)
    rounds = 16
    for _ in range(rounds):
        out, e = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P("pod"), P("pod")),
            out_specs=(P("pod"), P("pod"))))(jnp.asarray(g_local), e)
        acc += np.asarray(out)[0]
    drift = np.abs(acc / rounds - true_mean).max()
    assert drift <= scale * 0.15, drift
    print("PASS compressed_psum")


def check_sharded_train_step():
    """A reduced model train step under a real (2,4) mesh with the production
    sharding rules: must compile, run, and produce finite loss."""
    from repro.configs import get_reduced
    from repro.distributed import sharding as shd
    from repro.models import build_model
    from repro.train import optimizer as opt_mod
    from repro.train.loop import TrainConfig, make_train_step

    cfg = get_reduced("yi-6b")
    model = build_model(cfg)
    mesh = shd.make_mesh((2, 4), ("data", "model"))
    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        params = jax.device_put(params, shd.named_shardings(params, mesh))
        tcfg = TrainConfig(n_microbatches=2)
        state = opt_mod.init_opt_state(params, tcfg.opt)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                  cfg.vocab_size)
        batch = {"tokens": toks, "labels": toks}
        batch = jax.device_put(batch, NamedSharding(mesh, P("data", None)))
        step = jax.jit(make_train_step(model, tcfg), donate_argnums=(0, 1))
        p2, s2, metrics = step(params, state, batch)
        assert np.isfinite(float(metrics["total_loss"]))
        # the wq parameter kept its rule-prescribed sharding
        wq = p2["groups"]["blocks"]["pos0"]["attn"]["wq"]
        assert "model" in str(wq.sharding.spec), wq.sharding
    print("PASS sharded_train_step")


def check_pooled_decode():
    """Decode with the KV cache sharded on the sequence dim across `model`
    (flash-decoding / pooled memory) == single-device decode."""
    from repro.configs import get_reduced
    from repro.models import build_model

    cfg = get_reduced("yi-6b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    _, state = model.prefill(params, {"tokens": toks}, max_len=32)
    nxt = toks[:, -1:]
    cache_len = jnp.asarray(16, jnp.int32)
    ref_logits, _ = model.decode_step(params, nxt, state, cache_len)

    mesh = shd.make_mesh((2, 4), ("data", "model"))
    from repro.launch.dryrun import decode_shard_specs
    with jax.set_mesh(mesh):
        inputs = {"tokens": nxt, "state": state, "cache_len": cache_len}
        specs = decode_shard_specs(jax.eval_shape(lambda: inputs), mesh,
                                   batch=2)
        sharded = jax.device_put(inputs, specs)
        logits, _ = jax.jit(model.decode_step)(
            params, sharded["tokens"], sharded["state"], sharded["cache_len"])
    np.testing.assert_allclose(np.asarray(logits, np.float32),
                               np.asarray(ref_logits, np.float32),
                               rtol=3e-2, atol=3e-2)
    print("PASS pooled_decode")


def check_elastic_reshard_roundtrip():
    """Save on a (2,4) mesh, restore onto (4,2) — values identical."""
    import tempfile
    from repro.train.checkpoint import CheckpointManager
    from repro.distributed import sharding as shd

    state = {"w_gate": jax.random.normal(jax.random.PRNGKey(0), (64, 32))}
    mesh_a = jax.make_mesh((2, 4), ("data", "model"))
    mesh_b = jax.make_mesh((4, 2), ("data", "model"))
    sh_a = shd.named_shardings(state, mesh_a)
    sh_b = shd.named_shardings(state, mesh_b)
    placed = jax.device_put(state, sh_a)
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=1)
        mgr.save(1, placed, blocking=True)
        _, restored = mgr.restore(jax.eval_shape(lambda: state), shardings=sh_b)
    assert restored["w_gate"].sharding.mesh.shape["data"] == 4
    np.testing.assert_array_equal(np.asarray(restored["w_gate"]),
                                  np.asarray(state["w_gate"]))
    print("PASS elastic_reshard_roundtrip")


CHECKS = [check_mesh_device_count, check_moe_ep_matches_dense,
          check_moe_ep_capacity_drops, check_moe_partial_k_matches_dense,
          check_compressed_psum, check_sharded_train_step,
          check_pooled_decode, check_elastic_reshard_roundtrip]


if __name__ == "__main__":
    names = sys.argv[1:]
    for fn in CHECKS:
        if names and fn.__name__ not in names:
            continue
        fn()
    print("ALL_DIST_CHECKS_PASSED")
