"""Mixture-of-Experts: top-k routing, shared experts, expert parallelism.

Two interchangeable implementations (cross-checked in tests):

* ``dense``: GShard-style einsum over *all* experts — exact, differentiable,
  used for tiny CPU configs only (compute is E/k-fold redundant).
* ``ep``: production path. `shard_map` over the mesh: tokens stay on their
  (pod, data) shard, experts live on the `model` axis (E/16 per shard).
  Each expert shard sorts its local token->expert hits, runs the expert FFNs
  as grouped GEMMs (`jax.lax.ragged_dot`), scatters back, and the partial
  outputs are psum'd over `model`. Expert weights are additionally
  FSDP-sharded on `data` and all-gathered at use. Capacity: each shard
  processes at most ceil(cf * T_loc * k / n_shards) hits (global-capacity
  dropping; dropped hits contribute zero, like GShard).

The MemPool mapping: experts are "remote banks" — tokens access expert
weights resident on other chips' memory die, at group-level (ICI) latency.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import shard
from repro.models.config import ModelConfig
from repro.models.layers import cast, init_mlp, linear, mlp


def init_moe(cfg: ModelConfig, key) -> Dict:
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    ks = jax.random.split(key, 5)
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    p = {
        "router": jax.random.normal(ks[0], (d, e), jnp.float32) * s_in,
        "we_gate": jax.random.normal(ks[1], (e, d, f), jnp.float32) * s_in,
        "we_up": jax.random.normal(ks[2], (e, d, f), jnp.float32) * s_in,
        "we_down": jax.random.normal(ks[3], (e, f, d), jnp.float32) * s_out,
    }
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(ks[4], d, cfg.moe_d_ff * cfg.n_shared_experts)
    return p


def _route(router_w: jax.Array, xt: jax.Array, top_k: int):
    """Returns (gates (T,k) f32, idx (T,k) i32, probs (T,E) f32)."""
    logits = jnp.dot(xt.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, idx, probs


def _aux_loss(probs: jax.Array, idx: jax.Array, n_experts: int,
              batch_axes: Tuple[str, ...] = ()) -> jax.Array:
    """Load-balancing loss (Switch/GShard): E * sum_e f_e * P_e.

    Inside shard_map, ``batch_axes`` carries the mesh axes the token batch is
    split over; f_e/P_e are pmean'ed across them *before* the product, which
    makes the sharded aux numerically identical to the dense global one
    (means of equal-size shard means == global mean).
    """
    hits = jax.nn.one_hot(idx, n_experts, dtype=jnp.float32).sum(1)  # (T,E)
    f_e = hits.mean(0)
    p_e = probs.mean(0)
    for ax in batch_axes:
        f_e = jax.lax.pmean(f_e, ax)
        p_e = jax.lax.pmean(p_e, ax)
    return n_experts * jnp.sum(f_e * p_e)


# ------------------------------------------------------------------ dense

def _moe_dense(p: Dict, xt: jax.Array, cfg: ModelConfig):
    gates, idx, probs = _route(p["router"], xt, cfg.top_k)
    # every expert runs every token, in f32 (tiny CPU test configs only;
    # the CPU backend lacks bf16xbf16->f32 for batched dots)
    xf = xt.astype(jnp.float32)
    h = jnp.einsum("td,edf->tef", xf, p["we_gate"])
    u = jnp.einsum("td,edf->tef", xf, p["we_up"])
    y_e = jnp.einsum("tef,efd->ted", jax.nn.silu(h) * u, p["we_down"])
    w_te = (jax.nn.one_hot(idx, cfg.n_experts, dtype=jnp.float32)
            * gates[..., None]).sum(1)                      # (T,E)
    y = jnp.einsum("ted,te->td", y_e, w_te)
    return y.astype(xt.dtype), _aux_loss(probs, idx, cfg.n_experts)


# --------------------------------------------------------------------- ep

def _moe_ep_inner(xt, router_w, wg, wu, wd, *, cfg: ModelConfig,
                  n_shards: int, fsdp_axis: Optional[str],
                  batch_axes: Tuple[str, ...] = (),
                  partial_k: bool = False):
    """Per-shard body. xt: (T_loc, d); wg/wu/wd: (E_loc, d[/fsdp], f).

    Two data-movement modes (the paper's locality rule — move whichever is
    smaller):
      * weight-gather (train): tokens >> weights, so the d-sharded expert
        weights are all-gathered over the FSDP axis and tokens stay put;
      * partial-K token-gather (decode): a handful of tokens vs GBs of
        expert weights — the *tokens* are all-gathered to the stationary
        2D-sharded experts, partial-K GEMMs run on each d-slice, and
        activations psum over the FSDP axis. Weights never move.
    """
    if partial_k and fsdp_axis is not None:
        return _moe_ep_partial_k(xt, router_w, wg, wu, wd, cfg=cfg,
                                 n_shards=n_shards, fsdp_axis=fsdp_axis,
                                 batch_axes=batch_axes)
    if fsdp_axis is not None:
        wg = jax.lax.all_gather(wg, fsdp_axis, axis=1, tiled=True)
        wu = jax.lax.all_gather(wu, fsdp_axis, axis=1, tiled=True)
        wd = jax.lax.all_gather(wd, fsdp_axis, axis=2, tiled=True)
    t, d = xt.shape
    k, e = cfg.top_k, cfg.n_experts
    e_loc = e // n_shards
    rank = jax.lax.axis_index("model")

    gates, idx, probs = _route(router_w, xt, k)
    flat_e = idx.reshape(-1)
    flat_gate = gates.reshape(-1).astype(jnp.float32)
    flat_tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)

    lo = rank * e_loc
    is_local = (flat_e >= lo) & (flat_e < lo + e_loc)
    loc_e = jnp.where(is_local, flat_e - lo, e_loc)         # E_loc = overflow
    order = jnp.argsort(loc_e)                              # locals first
    cap = min(t * k, int(math.ceil(cfg.capacity_factor * t * k / n_shards)))
    sel = order[:cap]
    sel_e = loc_e[sel]
    sel_tok = flat_tok[sel]
    sel_gate = jnp.where(sel_e < e_loc, flat_gate[sel], 0.0)

    xs = jnp.take(xt, sel_tok, axis=0)                      # (cap, d)
    counts = jnp.bincount(sel_e, length=e_loc + 1)
    gs = jnp.concatenate([counts[:e_loc],
                          jnp.array([cap], jnp.int32) - counts[:e_loc].sum()[None]])
    # +1 zero expert absorbs overflow rows
    zg = jnp.zeros((1,) + wg.shape[1:], wg.dtype)
    zd = jnp.zeros((1,) + wd.shape[1:], wd.dtype)
    h = jax.lax.ragged_dot(xs, jnp.concatenate([cast(wg), cast(zg)]), gs,
                           preferred_element_type=jnp.float32)
    u = jax.lax.ragged_dot(xs, jnp.concatenate([cast(wu), cast(zg)]), gs,
                           preferred_element_type=jnp.float32)
    act = (jax.nn.silu(h) * u).astype(xt.dtype)
    out = jax.lax.ragged_dot(act, jnp.concatenate([cast(wd), cast(zd)]), gs,
                             preferred_element_type=jnp.float32)
    y = jnp.zeros((t, d), jnp.float32).at[sel_tok].add(out * sel_gate[:, None])
    # local scatter-add in f32 (exact); wire in bf16 — the expert-combine
    # psum is one of the two largest activation collectives (§Perf jamba/h2)
    y = jax.lax.psum(y.astype(xt.dtype), "model")

    aux = _aux_loss(probs, idx, e, batch_axes)
    return y, aux


def _moe_ep_partial_k(xt, router_w, wg, wu, wd, *, cfg: ModelConfig,
                      n_shards: int, fsdp_axis: str,
                      batch_axes: Tuple[str, ...]):
    """Token-gathering partial-K MoE (decode). See _moe_ep_inner docstring.

    xt: (T_loc, d) batch-sharded over ``fsdp_axis``; wg/wu: (E_loc, d/nf, f);
    wd: (E_loc, f, d/nf). Tokens are gathered (tiny), every device routes the
    full token set, runs its d-slice of the expert GEMMs, and partial sums
    travel instead of weights."""
    t_loc, d = xt.shape
    k, e = cfg.top_k, cfg.n_experts
    e_loc = e // n_shards
    dsh = wg.shape[1]                              # local d-slice width
    nf = d // dsh                                  # fsdp axis size
    rank_e = jax.lax.axis_index("model")
    rank_d = jax.lax.axis_index(fsdp_axis)

    xt_all = jax.lax.all_gather(xt, fsdp_axis, axis=0, tiled=True)  # (T, d)
    t = xt_all.shape[0]
    gates, idx, probs = _route(router_w, xt_all, k)
    flat_e = idx.reshape(-1)
    flat_gate = gates.reshape(-1).astype(jnp.float32)
    flat_tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)

    lo = rank_e * e_loc
    is_local = (flat_e >= lo) & (flat_e < lo + e_loc)
    loc_e = jnp.where(is_local, flat_e - lo, e_loc)
    order = jnp.argsort(loc_e)
    cap = min(t * k, int(math.ceil(cfg.capacity_factor * t * k / n_shards)))
    sel = order[:cap]
    sel_e = loc_e[sel]
    sel_tok = flat_tok[sel]
    sel_gate = jnp.where(sel_e < e_loc, flat_gate[sel], 0.0)

    xs = jnp.take(xt_all, sel_tok, axis=0)                     # (cap, d)
    xs_loc = jax.lax.dynamic_slice_in_dim(xs, rank_d * dsh, dsh, 1)
    counts = jnp.bincount(sel_e, length=e_loc + 1)
    gs = jnp.concatenate([counts[:e_loc],
                          jnp.array([cap], jnp.int32) - counts[:e_loc].sum()[None]])
    zg = jnp.zeros((1,) + wg.shape[1:], wg.dtype)
    zd = jnp.zeros((1,) + wd.shape[1:], wd.dtype)
    # partial-K over the local d-slice, completed by psum over the fsdp axis
    h = jax.lax.ragged_dot(xs_loc, jnp.concatenate([cast(wg), cast(zg)]), gs,
                           preferred_element_type=jnp.float32)
    u = jax.lax.ragged_dot(xs_loc, jnp.concatenate([cast(wu), cast(zg)]), gs,
                           preferred_element_type=jnp.float32)
    h = jax.lax.psum(h, fsdp_axis)
    u = jax.lax.psum(u, fsdp_axis)
    act = (jax.nn.silu(h) * u).astype(xt.dtype)
    out = jax.lax.ragged_dot(act, jnp.concatenate([cast(wd), cast(zd)]), gs,
                             preferred_element_type=jnp.float32)  # (cap, dsh)
    y_all = jnp.zeros((t, dsh), jnp.float32).at[sel_tok].add(
        out * sel_gate[:, None])
    y_all = jax.lax.psum(y_all, "model")           # complete over experts
    # back to my token rows, then assemble d from the slice shards
    y_mine = jax.lax.dynamic_slice_in_dim(y_all, rank_d * t_loc, t_loc, 0)
    y = jax.lax.all_gather(y_mine, fsdp_axis, axis=1, tiled=True)  # (T_loc,d)

    aux_axes = tuple(a for a in batch_axes if a != fsdp_axis)
    aux = _aux_loss(probs, idx, e, aux_axes)
    return y.astype(xt.dtype), aux


def _moe_ep(p: Dict, x3: jax.Array, cfg: ModelConfig, mesh):
    b, s, d = x3.shape
    # joint divisibility: axes are consumed left to right so the *product*
    # of included axis sizes divides the batch (pod=2 x data=16 needs b%32==0)
    batch_axes = []
    rem = b
    for a in ("pod", "data"):
        if a in mesh.axis_names and rem % mesh.shape[a] == 0:
            batch_axes.append(a)
            rem //= mesh.shape[a]
    batch_axes = tuple(batch_axes)
    n_shards = mesh.shape["model"]
    can_2d = "data" in mesh.axis_names and d % mesh.shape["data"] == 0

    # --- data-movement mode (the paper's locality rule, see _moe_ep_inner):
    # compare bytes moved by gathering weights vs gathering tokens+partials.
    t_tokens = b * s
    e_loc = cfg.n_experts // max(n_shards, 1)
    nf = mesh.shape["data"] if can_2d else 1
    weight_bytes = 3 * e_loc * d * cfg.moe_d_ff * 2            # bf16 gather
    t_all = t_tokens // max(
        int(np.prod([mesh.shape[a] for a in batch_axes])), 1) * nf
    cap_all = int(math.ceil(cfg.capacity_factor * t_all * cfg.top_k
                            / max(n_shards, 1)))
    token_bytes = (t_all * d * 2 + 4 * cap_all * cfg.moe_d_ff * 4
                   + 2 * t_all * d * 4)
    partial_k = can_2d and "data" in batch_axes and token_bytes < weight_bytes

    if partial_k:
        fsdp = "data"                         # weights stationary, 2D-sharded
        w_spec = P("model", "data", None)
        wd_spec = P("model", None, "data")
    else:
        fsdp = "data" if (can_2d and "data" not in batch_axes) else None
        # weights: experts on model; d optionally FSDP on data
        w_spec = P("model", fsdp, None)
        wd_spec = P("model", None, fsdp)
    bspec = batch_axes if batch_axes else None

    def inner(xl, rw, wg, wu, wd):
        t = xl.shape[0] * xl.shape[1]
        y, aux = _moe_ep_inner(xl.reshape(t, d), rw, wg, wu, wd, cfg=cfg,
                               n_shards=n_shards, fsdp_axis=fsdp,
                               batch_axes=batch_axes, partial_k=partial_k)
        return y.reshape(xl.shape), aux

    # check_vma=False: the FSDP all-gather of expert weights is value-
    # replicated over `data` but VMA inference conservatively marks gathered
    # outputs as varying, rejecting the (correct) replicated out_specs when
    # the token batch does not occupy the data axis (e.g. batch=1 decode).
    # Numerical equivalence with the dense path is asserted in
    # tests/dist_checks.py::check_moe_ep_matches_dense.
    y, aux = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(bspec, None, None), P(None, None),
                  w_spec, w_spec, wd_spec),
        out_specs=(P(bspec, None, None), P()),
        check_vma=False,
    )(x3, p["router"], p["we_gate"], p["we_up"], p["we_down"])
    return y, aux


# ------------------------------------------------------------------ public

def moe_block(p: Dict, x: jax.Array, *, cfg: ModelConfig,
              impl: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (y, aux_loss). Adds shared experts if configured."""
    b, s, d = x.shape
    mesh = jax.sharding.get_abstract_mesh()
    use_ep = (impl == "ep" or
              (impl == "auto" and "model" in mesh.axis_names and
               cfg.n_experts % mesh.shape["model"] == 0 and
               mesh.shape["model"] > 1))
    if use_ep:
        y, aux = _moe_ep(p, x, cfg, mesh)
    else:
        y, aux = _moe_dense(p, x.reshape(b * s, d), cfg)
        y = y.reshape(b, s, d)
    if cfg.n_shared_experts:
        y = y + mlp(p["shared"], x)
    return y, aux
