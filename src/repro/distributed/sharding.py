"""Sharding rules: FSDP x TP x EP over the hierarchical mesh.

MemPool's locality principle, at pod scale: the `model` axis (intra-pod ICI,
the "group" level) carries the high-traffic tensor-parallel and
expert-parallel collectives; the `data` axis carries FSDP parameter gathers
and gradient reduce-scatters; the `pod` axis (the "cluster" level,
lowest-bandwidth point-to-point links) carries only data-parallel gradient
reductions, optionally int8-compressed.

Rules are divisibility-aware: a dim that does not divide its mesh axis falls
back to replication (e.g. 4 KV heads on a 16-way model axis).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
from jax.sharding import PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"
POD_AXIS = "pod"


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None,
              explicit: bool = False) -> jax.sharding.Mesh:
    """jax.make_mesh with every axis Auto (GSPMD propagates shardings from
    the ``shard`` constraints) or, with ``explicit``, every axis Explicit."""
    kind = (jax.sharding.AxisType.Explicit if explicit
            else jax.sharding.AxisType.Auto)
    return jax.make_mesh(tuple(shape), tuple(axes), (kind,) * len(axes),
                         devices=devices)

#: batch-dimension sharding: span the pod axis too (multi-pod data
#: parallelism). shard()/_fix_spec drop axes absent from the ambient mesh,
#: so single-pod meshes see plain ("data",).
BATCH = (POD_AXIS, DATA_AXIS)

#: DP-dominant layout: the batch dim additionally spans `model`, hidden dims
#: replicate. Chosen by the planner for models whose TP activation
#: all-reduces would dominate the step (small dense models — the paper's
#: "co-explore capacity and interconnect placement" applied to parallelism).
BATCH_ALL = (POD_AXIS, DATA_AXIS, MODEL_AXIS)


def layout() -> str:
    """Activation layout: "tp" (model axis partitions hidden dims) or "dp"
    (model axis joins data parallelism; weights FSDP-gathered at use).
    Process-level, read at trace time — set by the launcher/dry-run."""
    import os
    return os.environ.get("REPRO_LAYOUT", "tp")


def _apply_layout(spec: Tuple) -> Tuple:
    if layout() != "dp":
        return spec
    out = []
    for names in spec:
        if names == BATCH:
            out.append(BATCH_ALL)
        elif names == MODEL_AXIS:
            out.append(None)                  # hidden dims replicate
        elif isinstance(names, tuple):
            kept = tuple(n for n in names if n != MODEL_AXIS)
            out.append(kept or None)
        else:
            out.append(names)
    return tuple(out)


def axis_size(mesh, name: str) -> int:
    try:
        return mesh.shape[name]
    except (KeyError, TypeError):
        return 1


def shard(x: jax.Array, *spec) -> jax.Array:
    """with_sharding_constraint that no-ops without an ambient mesh.

    Axis names absent from the mesh are dropped from the spec; dims that do
    not divide the axis size are replicated.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return x
    fixed = _fix_spec(_apply_layout(tuple(spec)), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, P(*fixed))


def _fix_spec(spec: Tuple, shape: Tuple[int, ...], mesh) -> Tuple:
    fixed = []
    for i, names in enumerate(spec):
        if names is None:
            fixed.append(None)
            continue
        names_t = names if isinstance(names, tuple) else (names,)
        names_t = tuple(n for n in names_t if n in mesh.axis_names)
        # greedy prefix: keep the longest leading run of axes whose product
        # divides the dim (e.g. batch=256 over (pod,data,model)=512 shards
        # over (pod,data)=32 instead of replicating entirely)
        kept = []
        prod = 1
        for n in names_t:
            size = axis_size(mesh, n)
            if shape[i] % (prod * size) == 0:
                kept.append(n)
                prod *= size
            else:
                break
        if not kept:
            fixed.append(None)
        else:
            fixed.append(tuple(kept) if len(kept) > 1 else kept[0])
    # pad/trim to rank
    fixed += [None] * (len(shape) - len(fixed))
    return tuple(fixed[:len(shape)])


def fix_spec_for(mesh, spec: P, shape: Tuple[int, ...]) -> P:
    """Public divisibility fixer for out-of-trace use (e.g. input shardings)."""
    return P(*_fix_spec(tuple(spec), shape, mesh))


def model_axis_size(mesh=None) -> int:
    """Size of the `model` axis on ``mesh`` (ambient if None); 1 without one."""
    if mesh is None:
        mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return 1
    return axis_size(mesh, MODEL_AXIS)


def heads_divide(n_heads: int, mesh=None) -> bool:
    """True iff the (ambient) model axis is > 1 and divides ``n_heads``.

    The gate for head-axis KV placement: the head dim of attention is
    batch-like (softmax and PV reduce over the *sequence* dim, which stays
    local), so a head-sharded cache computes exactly what a replicated one
    does, shard by shard — each mesh shard holds the pages its own heads
    read and never sees the others'. When heads do not divide, callers fall
    back to the seq-sharded (flash-decoding) layout."""
    m = model_axis_size(mesh)
    return m > 1 and n_heads % m == 0


def kernel_heads_shard(n_kv_heads: int) -> bool:
    """True iff a Pallas attention kernel over ``n_kv_heads`` KV heads can
    run under the ambient mesh: no multi-device mesh, or a `model` axis
    that divides the KV heads (see :func:`over_heads`). Otherwise callers
    take their jnp route, which GSPMD partitions."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.size <= 1:
        return True
    return n_kv_heads % model_axis_size(mesh) == 0


def over_heads(fn, arg_axes: Sequence[str]):
    """Run a Pallas kernel once per mesh shard, on that shard's slots and
    heads.

    GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned"). Attention is independent per slot and per
    head, so a shard_map that hands each shard its own slice computes
    exactly the unsharded result. ``arg_axes`` names, per argument, which
    of its leading axes split: ``"b"`` splits axis 0 (the slot batch) over
    :data:`BATCH`, ``"h"`` splits axis 1 (heads) over `model`, ``"bh"``
    both. The output is ``"bh"``. An axis that does not
    divide its mesh axes stays whole (:func:`fix_spec_for`). With GQA the
    query and KV head slices line up whenever :func:`kernel_heads_shard`
    holds. Identity without a multi-device mesh."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.size <= 1:
        return fn

    def spec(axes: str, shape) -> P:
        base = (BATCH if "b" in axes else None,
                MODEL_AXIS if "h" in axes else None)
        return fix_spec_for(mesh, P(*base[:len(shape)]), shape)

    def run(*args):
        in_specs = tuple(spec(a, x.shape) for a, x in zip(arg_axes, args))
        out = jax.eval_shape(fn, *args)
        return jax.shard_map(fn, in_specs=in_specs,
                             out_specs=spec("bh", out.shape),
                             check_vma=False)(*args)

    return run


# ---------------------------------------------------------------------------
# Parameter partitioning rules (by pytree path name patterns).
# ---------------------------------------------------------------------------

#: (substring pattern, spec builder). First match wins. Specs may be longer
#: than the param rank: stacked (scan) params get the leading axes skipped.
_RULES: Tuple[Tuple[str, P], ...] = (
    # embeddings: vocab on model (TP vocab-parallel logits), d on data (FSDP)
    ("embed", P(MODEL_AXIS, DATA_AXIS)),
    ("unembed", P(MODEL_AXIS, DATA_AXIS)),
    # attention
    ("wq_a", P(DATA_AXIS, MODEL_AXIS)),
    ("wq_b", P(DATA_AXIS, MODEL_AXIS)),
    ("wkv_a", P(DATA_AXIS, None)),
    ("wkv_b", P(DATA_AXIS, MODEL_AXIS)),
    ("wq", P(DATA_AXIS, MODEL_AXIS)),
    ("wk", P(DATA_AXIS, MODEL_AXIS)),
    ("wv", P(DATA_AXIS, MODEL_AXIS)),
    ("wo", P(MODEL_AXIS, DATA_AXIS)),
    ("bq", P(MODEL_AXIS)),
    ("bk", P(MODEL_AXIS)),
    ("bv", P(MODEL_AXIS)),
    # dense mlp
    ("w_gate", P(DATA_AXIS, MODEL_AXIS)),
    ("w_up", P(DATA_AXIS, MODEL_AXIS)),
    ("w_down", P(MODEL_AXIS, DATA_AXIS)),
    # moe: experts on model (EP), shared experts like dense mlp
    ("router", P(None, None)),
    ("we_gate", P(MODEL_AXIS, DATA_AXIS, None)),
    ("we_up", P(MODEL_AXIS, DATA_AXIS, None)),
    ("we_down", P(MODEL_AXIS, None, DATA_AXIS)),
    # mamba
    ("in_proj", P(DATA_AXIS, MODEL_AXIS)),
    ("conv_w", P(None, MODEL_AXIS)),
    ("conv_b", P(MODEL_AXIS)),
    ("x_proj", P(MODEL_AXIS, None)),
    ("dt_proj", P(None, MODEL_AXIS)),
    ("dt_bias", P(MODEL_AXIS)),
    ("a_log", P(MODEL_AXIS, None)),
    ("ssm_d", P(MODEL_AXIS)),
    ("out_proj", P(MODEL_AXIS, DATA_AXIS)),
)


#: KV-cache leaves carrying a head axis at rank-3 *from the right* — true in
#: BOTH layouts the serving engine uses: the dense slab ``(B, hkv, max_len,
#: hd)`` / stacked ``(r, B, hkv, max_len, hd)`` AND the paged pool
#: ``(n_pages, hkv, page_tokens, hd)`` / stacked ``(r, n_pages, hkv, pt,
#: hd)``. The page-indexed leading axis replicates (block tables address any
#: page from any shard's table row); only the head axis shards.
_CACHE_HEAD_LEAVES = frozenset({"k", "v"})

#: Cache leaves with no head axis: the MLA latent (shared across heads) and
#: recurrent SSM state (per-sequence). These replicate over `model` — which
#: is why MLA pool capacity does NOT scale with model shards (see
#: repro.serve.scheduler.kv_shards).
_CACHE_STATE_LEAVES = frozenset({"ckv", "krope", "conv", "ssm"})


def spec_for_cache(path: str, shape: Tuple[int, ...], mesh) -> Optional[P]:
    """PartitionSpec for a KV-cache / paged-pool leaf, or None if ``path``
    does not name one. Matches by final path component (exact leaf names,
    not substrings — ``wkv_a`` must not match ``k``)."""
    leaf = path.rsplit("/", 1)[-1]
    if leaf in _CACHE_HEAD_LEAVES and len(shape) >= 3:
        base = (None,) * (len(shape) - 3) + (MODEL_AXIS, None, None)
        return P(*_fix_spec(base, shape, mesh))
    if leaf in _CACHE_STATE_LEAVES:
        return P(*(None,) * len(shape))
    return None


def spec_for_param(path: str, shape: Tuple[int, ...], mesh) -> P:
    """PartitionSpec for one parameter, by name pattern + divisibility.

    Under the "infer" layout (decode serving), non-expert weights drop their
    `data`-axis (FSDP) factor and live TP-sharded but data-replicated: a
    decode step touches every dense weight for a handful of tokens, so
    gather-at-use traffic would dwarf the activations. Expert weights stay
    2D-sharded — too big to replicate — and the MoE layer gathers the
    *tokens* to the weights instead (repro.models.moe partial-K path)."""
    cache_spec = spec_for_cache(path, shape, mesh)
    if cache_spec is not None:
        return cache_spec
    for pat, spec in _RULES:
        if pat in path:
            base = tuple(spec)
            if layout() == "infer" and not pat.startswith("we_"):
                base = tuple(None if n == DATA_AXIS else n for n in base)
            # stacked scan params: leading (n_repeat,) axes -> replicate them
            extra = len(shape) - len(base)
            if extra > 0:
                base = (None,) * extra + base
            elif extra < 0:
                base = base[-len(shape):] if shape else ()
            return P(*_fix_spec(base, shape, mesh))
    return P(*_fix_spec((None,) * len(shape), shape, mesh))


def param_specs(params: Any, mesh) -> Any:
    """Spec pytree matching ``params`` (works on arrays or ShapeDtypeStructs)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for path, leaf in flat:
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        specs.append(spec_for_param(name.lower(), leaf.shape, mesh))
    return jax.tree_util.tree_unflatten(treedef, specs)


def named_shardings(params: Any, mesh) -> Any:
    specs = param_specs(params, mesh)
    return jax.tree_util.tree_map(
        lambda s: jax.sharding.NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))
