"""Train step factory: mixed precision, grad-accumulation scan, remat,
optional compressed cross-pod gradient sync.

Memory layout per device (the capacity budget the planner reasons about):
f32 master params + moments (FSDP-sharded), bf16 compute copies (transient),
one superblock of activations (remat) x microbatch. Microbatch count is the
knob that trades activation stash against per-step launch overhead — the
direct analogue of the paper's tile-size/static-overhead tradeoff.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed import collectives
from repro.distributed import sharding as shd
from repro.models.api import Model
from repro.train import optimizer as opt_mod


#: params kept in f32 for compute even under mixed precision (numerics):
#: norm scales (1-D anyway), SSM decay logs, dt bias, router logits.
_F32_PARAM_NAMES = ("a_log", "scale", "dt_bias", "router")


def _cast_params_for_compute(params):
    """bf16 compute copies, CONSTRAINED to the parameter shardings.

    Pinning the cast output to the param's own (FSDP x TP) spec makes GSPMD
    place the FSDP all-gather AFTER the convert — weights travel the wire in
    bf16, halving gather bytes vs gathering f32 then casting (§Perf,
    qwen2.5/h3). Numerics are unchanged: layers already cast weights to
    bf16 at use; this moves the cast before the gather."""
    mesh = jax.sharding.get_abstract_mesh()
    have_mesh = bool(mesh.axis_names)
    flat, tdef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for path, p in flat:
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path).lower()
        keep_f32 = (p.ndim < 2 or p.dtype != jnp.float32
                    or any(n in name for n in _F32_PARAM_NAMES))
        if keep_f32:
            out.append(p)
            continue
        pb = p.astype(jnp.bfloat16)
        if have_mesh:
            pb = jax.lax.with_sharding_constraint(
                pb, shd.spec_for_param(name, p.shape, mesh))
        out.append(pb)
    return jax.tree_util.tree_unflatten(tdef, out)


def _constrain_grads_like_params(grads, params):
    """Pin gradient shardings to the parameter shardings at the point of
    production, so GSPMD lowers the DP gradient reduction as a
    reduce-scatter onto the FSDP shards (half the wire bytes of the
    all-reduce it otherwise coalesces). §Perf hypothesis log, qwen2.5/h2."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return grads
    specs = shd.param_specs(params, mesh)
    return jax.tree.map(
        lambda g, s: jax.lax.with_sharding_constraint(g, s), grads, specs)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    opt: opt_mod.OptConfig = opt_mod.OptConfig()
    n_microbatches: int = 1
    remat: bool = True
    compress_pod_grads: bool = False  # int8+EF gradient sync across pods


def _split_micro(batch: Dict[str, jax.Array], n: int) -> Dict[str, jax.Array]:
    """(B, ...) -> (n, B/n, ...) for the accumulation scan."""
    def f(x):
        b = x.shape[0]
        assert b % n == 0, (b, n)
        return x.reshape(n, b // n, *x.shape[1:])
    return jax.tree.map(f, batch)


def make_train_step(model: Model, tcfg: TrainConfig):
    """Returns train_step(params, opt_state, batch) -> (params, opt, metrics).

    ``batch`` arrays are (global_batch, ...); sharding comes from the caller's
    in_shardings / ambient mesh.
    """

    def loss_fn(params, micro_batch):
        params_c = _cast_params_for_compute(params)
        loss, metrics = model.loss(params_c, micro_batch, remat=tcfg.remat)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def compute_grads(params, batch):
        if tcfg.n_microbatches <= 1:
            (loss, metrics), grads = grad_fn(params, batch)
            return loss, metrics, _constrain_grads_like_params(grads, params)
        micro = _split_micro(batch, tcfg.n_microbatches)
        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        zero = _constrain_grads_like_params(zero, params)

        def acc_step(carry, mb):
            g_acc, l_acc = carry
            (loss, metrics), grads = grad_fn(params, mb)
            grads = _constrain_grads_like_params(grads, params)
            g_acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32),
                                 g_acc, grads)
            return (g_acc, l_acc + loss), metrics

        (g_acc, l_sum), metrics = jax.lax.scan(
            acc_step, (zero, jnp.zeros(())), micro)
        n = tcfg.n_microbatches
        grads = jax.tree.map(lambda g: g / n, g_acc)
        metrics = jax.tree.map(lambda m: m[-1], metrics)
        return l_sum / n, metrics, grads

    def train_step(params, opt_state, batch):
        loss, metrics, grads = compute_grads(params, batch)
        new_params, new_opt, opt_metrics = opt_mod.adamw_update(
            params, grads, opt_state, tcfg.opt)
        metrics = dict(metrics, **opt_metrics, total_loss=loss)
        return new_params, new_opt, metrics

    return train_step


def make_compressed_train_step(model: Model, tcfg: TrainConfig, mesh):
    """Variant for multi-pod meshes: per-pod gradients are computed under
    GSPMD (data/model stay auto-sharded), then synced across the `pod` axis
    with int8 + error feedback inside a shard_map restricted to `pod`.

    State gains an ``err`` tree (error-feedback residuals, pod-local).
    """
    from jax.sharding import PartitionSpec as P
    n_pods = mesh.shape["pod"]

    def loss_fn(params, batch):
        loss, metrics = model.loss(params, batch, remat=tcfg.remat)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(params, opt_state, err, batch):
        def per_pod(params, err, batch):
            (loss, metrics), grads = grad_fn(params, batch)
            grads, new_err = collectives.compressed_grad_sync(
                grads, err, "pod", n_pods)
            loss = jax.lax.pmean(loss, "pod")
            metrics = jax.lax.pmean(metrics, "pod")
            return loss, metrics, grads, new_err

        pspec = jax.tree.map(lambda _: P(), params)
        loss, metrics, grads, new_err = jax.shard_map(
            per_pod, mesh=mesh,
            in_specs=(pspec, pspec, P("pod")),
            out_specs=(P(), P(), pspec, pspec),
            axis_names={"pod"},
        )(params, err, batch)
        new_params, new_opt, opt_metrics = opt_mod.adamw_update(
            params, grads, opt_state, tcfg.opt)
        metrics = dict(metrics, **opt_metrics, total_loss=loss)
        return new_params, new_opt, new_err, metrics

    return train_step
