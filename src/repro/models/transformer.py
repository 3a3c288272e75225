"""Decoder-only LM: init / forward / loss / prefill / decode.

The layer stack is factorized into repeated superblocks
(:meth:`ModelConfig.layer_groups`) and driven with `jax.lax.scan` over stacked
params — HLO size stays constant in depth, which keeps the 512-device dry-run
compiles tractable. Heterogeneous patterns (gemma3 5:1 local:global, jamba
1:7+MoE) unroll *inside* the superblock; homogeneous stacks get a period-1
pattern automatically.

Remat: each superblock body is `jax.checkpoint`ed (policy configurable), so
backward memory is one superblock's activations + the per-superblock carried
x — the scan-remat standard.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.planner import KernelPlans
from repro.distributed.sharding import BATCH, shard
from repro.models import attention as attn_mod
from repro import runtime_flags
from repro.models import layers, moe as moe_mod, ssm
from repro.models.config import LayerGroup, LayerKind, ModelConfig

Params = Dict[str, Any]


# ----------------------------------------------------------------- init

def _init_layer(cfg: ModelConfig, kind: LayerKind, key) -> Params:
    ks = jax.random.split(key, 2)
    p: Params = {"ln1": layers.init_rmsnorm(cfg.d_model)}
    if kind.attn == "mamba":
        p["mamba"] = ssm.init_mamba(cfg, ks[0])
        if kind.mlp != "none":
            p["ln2"] = layers.init_rmsnorm(cfg.d_model)
    else:
        p["attn"] = attn_mod.INIT[kind.attn](cfg, ks[0])
        p["ln2"] = layers.init_rmsnorm(cfg.d_model)
    if kind.mlp == "mlp":
        p["mlp"] = layers.init_mlp(ks[1], cfg.d_model, cfg.d_ff)
    elif kind.mlp == "moe":
        p["moe"] = moe_mod.init_moe(cfg, ks[1])
    return p


def _stack_init(init_fn, n: int, key):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def init_lm(cfg: ModelConfig, key) -> Params:
    k_emb, k_layers, k_enc = jax.random.split(key, 3)
    params: Params = {
        "tok": layers.init_embed(k_emb, cfg.padded_vocab, cfg.d_model,
                                 tie=cfg.tie_embeddings),
        "final_norm": layers.init_rmsnorm(cfg.d_model),
        "groups": {},
    }
    for gi, group in enumerate(cfg.layer_groups()):
        gkey = jax.random.fold_in(k_layers, gi)
        gp = {}
        for pos, kind in enumerate(group.pattern):
            pkey = jax.random.fold_in(gkey, pos)
            gp[f"pos{pos}"] = _stack_init(
                functools.partial(_init_layer, cfg, kind), group.n_repeat, pkey)
        params["groups"][group.name] = gp
    return params


# ----------------------------------------------------------- layer apply

def _apply_layer(cfg: ModelConfig, kind: LayerKind, p: Params, x: jax.Array,
                 *, positions, positions3, cache, cache_len,
                 plans: Optional[KernelPlans] = None, block_tables=None,
                 layer=None):
    """Returns (x, aux, new_cache)."""
    aux = jnp.zeros((), jnp.float32)
    h = layers.rmsnorm(p["ln1"], x, cfg.norm_eps)
    if kind.attn == "mamba":
        # recurrent state is per-slot resident, never paged — block tables
        # only address the attention page pools
        y, new_attn_cache = ssm.mamba_block(
            p["mamba"], h, cfg=cfg, cache=cache,
            plan=plans.scan_chunk if plans else None)
    else:
        y, new_attn_cache = attn_mod.APPLY[kind.attn](
            p["attn"], h, cfg=cfg, kind=kind, positions=positions,
            positions3=positions3, cache=cache, cache_len=cache_len,
            plan=plans.attention if plans else None,
            block_tables=block_tables, layer=layer)
    x = x + y
    if kind.mlp == "mlp":
        x = x + layers.mlp(p["mlp"], layers.rmsnorm(p["ln2"], x, cfg.norm_eps))
    elif kind.mlp == "moe":
        y, aux = moe_mod.moe_block(p["moe"],
                                   layers.rmsnorm(p["ln2"], x, cfg.norm_eps),
                                   cfg=cfg)
        x = x + y
    return x, aux, new_attn_cache


def _superblock(cfg: ModelConfig, group: LayerGroup, stacked: Params,
                x: jax.Array, caches, cache_len, positions, positions3,
                aux: jax.Array, plans: Optional[KernelPlans] = None,
                block_tables=None, layer=None):
    """Apply repetition ``layer`` of ``group.pattern``. ``stacked`` and the
    scanned caches are that repetition's slices (no leading axis here);
    the paged pool's leaves are whole, read and written at ``layer``."""
    new_caches = {}
    for pos, kind in enumerate(group.pattern):
        cache_i = caches.get(f"pos{pos}") if caches else None
        x, aux_i, nc = _apply_layer(cfg, kind, stacked[f"pos{pos}"], x,
                                    positions=positions, positions3=positions3,
                                    cache=cache_i, cache_len=cache_len,
                                    plans=plans, block_tables=block_tables,
                                    layer=layer)
        aux = aux + aux_i
        if nc is not None:
            new_caches[f"pos{pos}"] = nc
    return x, aux, new_caches


def _split_pool(group: LayerGroup, g_caches, paged_pool: bool):
    """``(pool, scanned)``: a group's paged attention caches, which the
    layer scan carries whole, and the rest, which it slices per repetition
    (``xs``) and restacks (``ys``).

    In a paged pool every attention position's leaves are the
    stacked page pool ``(n_repeat, n_pages, ...)``: carried, each step
    writes only its new rows into it and the walk reads repetition ``l``
    where it lies, so a donated pool is updated in place instead of being
    sliced out and rebuilt beside itself. Recurrent state and the dense
    slot slab are per slot and stay scanned."""
    if g_caches is None or not paged_pool:
        return {}, g_caches
    paged = {f"pos{i}" for i, kind in enumerate(group.pattern)
             if kind.attn != "mamba"}
    return ({pos: c for pos, c in g_caches.items() if pos in paged},
            {pos: c for pos, c in g_caches.items() if pos not in paged})


def pool_leaves(cfg: ModelConfig, caches) -> Dict[str, int]:
    """Of the paged pool's leaves (every leaf of an attention position, as
    :func:`paged_cache_kinds` walks them), how many the decode forward's
    layer scan carries in place (:func:`_split_pool`)."""
    paged = sum(len(jax.tree.leaves(caches[g][pos]))
                for g, pos, is_paged in paged_cache_kinds(cfg) if is_paged)
    in_place = sum(
        len(jax.tree.leaves(_split_pool(group, caches[group.name], True)[0]))
        for group in cfg.layer_groups())
    return {"pool_leaves_in_place": in_place, "pool_leaves_paged": paged}


def _run_groups(cfg: ModelConfig, params: Params, x: jax.Array, *,
                positions, positions3=None, caches=None, cache_len=None,
                remat: bool = True, plans: Optional[KernelPlans] = None,
                block_tables=None):
    aux = jnp.zeros((), jnp.float32)
    new_caches: Dict[str, Any] = {}
    for group in cfg.layer_groups():
        stacked = params["groups"][group.name]
        pool, g_caches = _split_pool(
            group, caches.get(group.name) if caches else None,
            block_tables is not None)

        def body(carry, xs, _group=group):
            xc, auxc, layer, pool_c = carry
            p_slice, c_slice = xs
            xo, auxo, nc = _superblock(cfg, _group, p_slice, xc,
                                       {**(c_slice or {}), **pool_c},
                                       cache_len, positions, positions3, auxc,
                                       plans, block_tables, layer)
            pool_c = {pos: nc.pop(pos) for pos in pool_c}
            return (xo, auxo, layer + 1, pool_c), nc

        if remat:
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable)
        (x, aux, _, pool), nc_stacked = jax.lax.scan(
            body, (x, aux, jnp.zeros((), jnp.int32), pool),
            (stacked, g_caches),
            unroll=runtime_flags.scan_unroll(group.n_repeat))
        if caches is not None:
            new_caches[group.name] = {**nc_stacked, **pool}
    return x, aux, new_caches


# ----------------------------------------------------------------- public

def forward(cfg: ModelConfig, params: Params, tokens: jax.Array, *,
            frontend_embeds: Optional[jax.Array] = None,
            caches=None, cache_len=None, remat: bool = True,
            positions: Optional[jax.Array] = None,
            plans: Optional[KernelPlans] = None,
            block_tables: Optional[jax.Array] = None):
    """tokens: (B, S) int32. Optional frontend prefix embeds (B, Sf, d) are
    concatenated before the token embeddings (vlm/audio stubs).

    Returns (logits_f32 (B, S_total, padded_vocab), aux, new_caches).
    """
    x = layers.embed(params["tok"], tokens)
    if frontend_embeds is not None:
        x = jnp.concatenate([frontend_embeds.astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    x = shard(x, BATCH, None, None)
    if positions is None:
        start = cache_len if cache_len is not None else 0
        if isinstance(start, jax.Array) and start.ndim == 1:
            # per-slot cache lengths (B,): each row continues from its own
            # frontier (continuous batching — see DESIGN.md §Serving)
            start = start[:, None]
        positions = start + jnp.arange(s, dtype=jnp.int32)[None, :]
        positions = jnp.broadcast_to(positions, (b, s))
    positions3 = None
    if cfg.mrope:
        positions3 = jnp.broadcast_to(positions[None], (3, b, s))
    x, aux, new_caches = _run_groups(cfg, params, x, positions=positions,
                                     positions3=positions3, caches=caches,
                                     cache_len=cache_len, remat=remat,
                                     plans=plans, block_tables=block_tables)
    x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, aux, new_caches


def lm_loss(cfg: ModelConfig, params: Params, tokens: jax.Array,
            labels: jax.Array, *, frontend_embeds=None, remat: bool = True,
            loss_chunk: int = 2048,
            plans: Optional[KernelPlans] = None
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Causal LM loss. labels: (B, S) int32, -1 = ignore. The vocab
    projection + softmax runs in sequence chunks so the (tokens x vocab)
    logits tensor never materializes whole (capacity-aware, VMEM-sized)."""
    x, aux, _ = forward(cfg, params, tokens, frontend_embeds=frontend_embeds,
                        remat=remat, plans=plans)
    if frontend_embeds is not None:
        pad = jnp.full(frontend_embeds.shape[:2], -1, labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)
    b, s, d = x.shape
    chunk = min(loss_chunk, s)
    while s % chunk:
        chunk //= 2
    xc = x.reshape(b, s // chunk, chunk, d).swapaxes(0, 1)
    lc = labels.reshape(b, s // chunk, chunk).swapaxes(0, 1)

    def chunk_loss(carry, inp):
        xi, li = inp
        logits = layers.unembed_logits(params["tok"], xi)     # (B,c,Vpad) f32
        logits = logits.astype(jnp.float32)
        # mask padded vocab
        neg = jnp.finfo(jnp.float32).min
        v = cfg.vocab_size
        col = jnp.arange(cfg.padded_vocab)
        logits = jnp.where(col[None, None, :] < v, logits, neg)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(li, 0)[..., None], axis=-1)[..., 0]
        valid = (li >= 0).astype(jnp.float32)
        nll = (lse - gold) * valid
        tot, cnt = carry
        return (tot + nll.sum(), cnt + valid.sum()), None

    body = jax.checkpoint(
        chunk_loss,
        policy=jax.checkpoint_policies.save_only_these_names("unembed_table"),
    ) if remat else chunk_loss
    (tot, cnt), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())), (xc, lc),
                                 unroll=runtime_flags.scan_unroll(s // chunk))
    loss = tot / jnp.maximum(cnt, 1.0)
    total = loss + cfg.router_aux_weight * aux
    return total, {"loss": loss, "aux": aux, "tokens": cnt}


# -------------------------------------------------------------- caches

def init_caches(cfg: ModelConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Per-layer decode caches, grouped ``{group: {posN: {leaf: array}}}``.

    The leaf names are a sharding contract, not just labels:
    ``repro.distributed.sharding.spec_for_cache`` matches ``k``/``v``
    (head axis at rank-3 from the right -> sharded over `model` under a
    mesh) and ``ckv``/``krope``/``conv``/``ssm`` (no head axis ->
    replicated) by exact final path component. Renaming a leaf silently
    demotes that cache to replicated placement and desyncs the
    per-shard pool budgets in ``repro.serve.scheduler.kv_shards``.
    """
    caches: Dict[str, Any] = {}
    for group in cfg.layer_groups():
        g: Dict[str, Any] = {}
        for pos, kind in enumerate(group.pattern):
            if kind.attn == "mamba":
                one = ssm.init_mamba_cache(cfg, batch)
            elif kind.attn == "mla":
                one = attn_mod.init_mla_cache(cfg, batch, max_len, dtype)
            else:
                # NOTE: window layers could use a rotating window-sized cache;
                # we keep max_len and shard the seq dim instead (pooled KV) —
                # the rotating-buffer variant is logged as a §Perf candidate.
                one = attn_mod.init_gqa_cache(cfg, batch, max_len, dtype)
            g[f"pos{pos}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (group.n_repeat,) + a.shape), one)
        caches[group.name] = g
    return caches


def init_paged_caches(cfg: ModelConfig, batch: int, n_pages: int,
                      page_tokens: int, dtype=jnp.bfloat16,
                      quant_scales: bool = False) -> Dict[str, Any]:
    """Paged two-tier pool caches: attention layers share a flat page pool
    (``n_pages`` pages of ``page_tokens`` tokens, page 0 = null); recurrent
    SSM state stays per-slot resident exactly as in :func:`init_caches`.

    ``dtype``/``quant_scales`` come from the tier's codec (DESIGN.md
    §Tiered KV compression & host parking): an int8 tier stores codes in
    the page leaves plus one f32 amax scale per page in sibling
    ``*_scale`` leaves; recurrent state never quantizes (the scheduler
    rejects quantized codecs for recurrent families upstream)."""
    caches: Dict[str, Any] = {}
    for group in cfg.layer_groups():
        g: Dict[str, Any] = {}
        for pos, kind in enumerate(group.pattern):
            if kind.attn == "mamba":
                one = ssm.init_mamba_cache(cfg, batch)
            elif kind.attn == "mla":
                one = attn_mod.init_mla_pages(cfg, n_pages, page_tokens,
                                              dtype, quant_scales)
            else:
                one = attn_mod.init_gqa_pages(cfg, n_pages, page_tokens,
                                              dtype, quant_scales)
            g[f"pos{pos}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (group.n_repeat,) + a.shape), one)
        caches[group.name] = g
    return caches


def paged_cache_kinds(cfg: ModelConfig):
    """Yield ``(group_name, pos_key, is_paged)`` for every cache entry —
    the walk order engine-side spill/restore and page scatter share.
    ``is_paged`` is False for recurrent (per-slot resident) entries."""
    for group in cfg.layer_groups():
        for pos, kind in enumerate(group.pattern):
            yield group.name, f"pos{pos}", kind.attn != "mamba"


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def prefill(cfg: ModelConfig, params: Params, tokens: jax.Array,
            max_len: int, *, frontend_embeds=None,
            plans: Optional[KernelPlans] = None,
            caches=None, prefix_len=0):
    """Run the prompt, building caches. Returns (x_last, caches).

    ``caches``/``prefix_len`` enable *resumed* prefill — prefix-share
    suffixes and chunked-prefill chunks: ``caches`` already holds the K/V
    of the first ``prefix_len`` positions (gathered from shared pages or
    the request's own earlier chunks), ``tokens`` is only the tail, and
    RoPE positions/causal masks start at ``prefix_len``. A python-int
    ``prefix_len`` is jit-specialized (one compile per offset — the suffix
    path); a traced int32 scalar rides into the mask/position arithmetic
    instead (one compile per chunk-length bucket — the chunked path). Both
    route multi-token tails through the SAME blockwise prefill attention,
    so a resumed row's math is bit-identical to the same row of a full
    prefill.
    """
    if caches is None:
        caches = init_caches(cfg, tokens.shape[0], max_len)
    if not isinstance(prefix_len, jax.Array):
        prefix_len = int(prefix_len)
    x, aux, caches = forward(cfg, params, tokens,
                             frontend_embeds=frontend_embeds,
                             caches=caches, cache_len=prefix_len,
                             remat=False, plans=plans)
    return x, caches


def decode_step(cfg: ModelConfig, params: Params, tokens: jax.Array,
                caches, cache_len: jax.Array,
                plans: Optional[KernelPlans] = None,
                block_tables: Optional[jax.Array] = None):
    """One decode step. tokens: (B, 1); cache_len: scalar or per-slot (B,)
    filled-prefix lengths. With ``block_tables`` (B, P) the caches are the
    paged pool. Returns (logits (B,1,Vpad), caches)."""
    x, _, new_caches = forward(cfg, params, tokens, caches=caches,
                               cache_len=cache_len, remat=False, plans=plans,
                               block_tables=block_tables)
    logits = layers.unembed_logits(params["tok"], x)
    return logits, new_caches


def verify_step(cfg: ModelConfig, params: Params, tokens: jax.Array,
                caches, cache_len: jax.Array,
                plans: Optional[KernelPlans] = None,
                block_tables: Optional[jax.Array] = None):
    """Multi-position decode for speculative verify (DESIGN.md
    §Speculative decoding).

    ``tokens`` is ``(B, k+1)`` — each slot's last emitted token followed by
    its k draft tokens — and ``cache_len`` the per-slot ``(B,)`` frontier
    vector. Column ``j`` runs at RoPE position ``cache_len + j`` with a
    causal-within-chunk mask over the (dense or paged) cache, and its K/V
    is appended at ``cache_len + j``; logits column ``j`` therefore scores
    the token AFTER ``tokens[:, :j+1]`` exactly as ``j`` successive
    single-token :func:`decode_step` calls would — greedy acceptance is
    bit-exact by construction. Rejected suffix K/V stays behind the
    rolled-back frontier: masked like any stale row, overwritten as decode
    advances. This is :func:`decode_step` at S == k+1; the wrapper exists
    so the verify contract is named at every layer it threads through.
    """
    return decode_step(cfg, params, tokens, caches, cache_len, plans=plans,
                       block_tables=block_tables)
