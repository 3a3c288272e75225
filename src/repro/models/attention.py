"""Attention variants: GQA (+ sliding window, RoPE/M-RoPE), MLA (DeepSeek-V2),
with functional KV caches for decode.

Pooled-memory decode (the MemPool idea at pod scale): when the KV head count
divides the `model` axis, decode caches are placed on the *head* axis — each
mesh shard holds exactly the cache (or page) slice its own heads read, which
is bit-exact with the replicated layout because softmax/PV reduce over the
local seq dim (DESIGN.md §Sharded serving). Otherwise KV caches fall back to
*sequence*-dimension sharding across `model` (and `data` too when batch
cannot shard, e.g. long_500k's batch=1), where the attention math is written
so GSPMD turns the softmax reductions into partial max/sum + psum over the
cache shards — flash-decoding across chips, i.e. remote "banks" at the group
level of the hierarchy.

Two cache layouts share the math:

  * **dense slot slab** — ``(B, ..., max_len, ...)``, one worst-case-deep
    slab per slot. This is the oracle path.
  * **paged pool** — ``(n_pages, ..., page_tokens, ...)``, a flat page pool
    addressed through per-slot block tables (the two-tier pool of
    DESIGN.md §Paged two-tier pool). Writes resolve
    ``cache_len -> (physical page, offset)`` through the block table;
    reads walk the table (:mod:`repro.kernels.paged_attention`). A paged
    decode is bit-exact with the dense one: the gather reassembles the
    same contiguous view the slab holds.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import tiling
from repro.distributed.sharding import (BATCH, MODEL_AXIS, heads_divide,
                                        shard)
from repro.kernels import ops
from repro.kernels.paged_attention import (INT8_QMAX,
                                           decode_attention_masked,
                                           gather_kv_pages,
                                           paged_decode_attention)
from repro.models import layers
from repro.models.config import LayerKind, ModelConfig
from repro.models.layers import cast, linear


def _cache_write(cache_arr: jax.Array, new: jax.Array, cache_len,
                 axis: int) -> jax.Array:
    """Append ``new`` into the cache at position ``cache_len``.

    Single-token traced writes use a masked select instead of
    dynamic_update_slice: a DUS with a traced start on the SEQ-SHARDED cache
    dim forces GSPMD to replicate (all-gather) the whole cache per layer —
    the dominant decode collective before this fix (§Perf, decode/h2). The
    elementwise select keeps the pooled (seq-sharded) layout intact.

    ``cache_len`` may be a scalar (every row at the same offset — one-shot
    generate) or a per-row vector ``(B,)`` (continuous batching: each KV
    slot has its own filled length). The vector case broadcasts against the
    batch axis (axis 0 of every cache array) and also accepts multi-token
    ``new`` — the speculative verify chunk appends k+1 candidate rows at
    ``cache_len + j`` per slot (DESIGN.md §Speculative decoding); rejected
    rows stay behind the rolled-back frontier, masked like any stale K/V.
    """
    new = new.astype(cache_arr.dtype)
    s = new.shape[axis]
    if isinstance(cache_len, jax.Array) and (s == 1 or cache_len.ndim == 1):
        iota = jax.lax.broadcasted_iota(jnp.int32, cache_arr.shape, axis)
        if cache_len.ndim == 1:      # per-slot lengths: (B,) over batch axis 0
            cache_len = cache_len.reshape(
                (-1,) + (1,) * (cache_arr.ndim - 1))
        if s == 1:
            return jnp.where(iota == cache_len, new, cache_arr)
        # multi-token per-slot append: position cache_len + j takes row j of
        # ``new`` — a masked gather-select, so each slot writes at its own
        # offset without a (replicating) per-row DUS
        idx = jnp.clip(iota - cache_len, 0, s - 1)
        gathered = jnp.take_along_axis(new, idx, axis=axis)
        return jnp.where((iota >= cache_len) & (iota < cache_len + s),
                         gathered, cache_arr)
    return jax.lax.dynamic_update_slice_in_dim(cache_arr, new,
                                               cache_len, axis)


def _paged_cache_write(pages: jax.Array, new: jax.Array,
                       cache_len: jax.Array, block_tables: jax.Array,
                       axis: int, *, layer=None) -> jax.Array:
    """Block-table-aware token append into the paged pool.

    ``pages`` is ``(n_pages, *page_shape)`` with the token axis at ``axis``
    inside a page (GQA: 1, MLA: 0), or, with ``layer``, the stacked pool
    ``(L, n_pages, *page_shape)`` the decode chunk's layer scan carries:
    the rows land at ``[layer, page, ..., offset]`` and nothing else of the
    pool is touched, so a donated pool is updated in place. ``new`` is the
    dense write
    ``(B, ..., s, ...)`` — ``s == 1`` for ordinary decode, ``s == k+1`` for
    a speculative verify chunk. Each row's token ``j`` resolves
    ``cache_len + j -> (physical page, in-page offset)`` through its
    block-table row, so a verify chunk's writes cross page boundaries
    correctly. Rows whose frontier is at or past the mapped depth (a
    drained slot's frozen decode) are routed to the reserved null page 0 —
    the paged analogue of the dense iota-select writing nowhere.

    With prefix sharing, the pages these writes resolve to are private to
    the row BY SCHEDULER INVARIANT: shared (ref-counted) pages sit strictly
    behind the frontier and the copy-on-write rule gives every request its
    own frontier page at admission (DESIGN.md §Prefix sharing &
    copy-on-write) — so no guard is needed here.
    """
    lead = () if layer is None else (layer,)
    pt = pages.shape[len(lead) + 1 + axis]
    p_max = block_tables.shape[1]
    s = new.shape[1 + axis]
    new = new.astype(pages.dtype)
    for j in range(s):
        pos = cache_len + j
        logical = jnp.minimum(pos // pt, p_max - 1)
        phys = jnp.take_along_axis(block_tables, logical[:, None],
                                   axis=1)[:, 0]
        phys = jnp.where(pos < p_max * pt, phys, 0)
        off = pos % pt
        if axis == 0:
            pages = pages.at[lead + (phys, off)].set(new[:, j])
        else:
            # the row's whole page, its token set at ``off``: a write whose
            # window is a page keeps the pool in the layout the walk reads
            at = lead + (phys,)
            page = pages[at]                               # (B, H, pt, D)
            iota = jax.lax.broadcasted_iota(jnp.int32, page.shape, 2)
            page = jnp.where(iota == off[:, None, None, None],
                             new[:, :, j, None], page)
            pages = pages.at[at].set(page)
    return pages


def _paged_cache_write_q(pages: jax.Array, scales: jax.Array, new: jax.Array,
                         cache_len: jax.Array, block_tables: jax.Array,
                         axis: int, *, layer=None
                         ) -> Tuple[jax.Array, jax.Array]:
    """Int8 block-table token append with a monotone per-page scale.

    Same ``cache_len + j -> (page, offset)`` resolution as
    :func:`_paged_cache_write`, and the same ``layer`` index into a stacked
    pool (scales ``(L, n_pages)``), but the pool holds int8 codes plus one
    f32 amax-scale per page (DESIGN.md §Tiered KV compression & host
    parking).
    Per appended token: the page's scale grows to cover the new value
    (``max(old, amax(new)/127)`` — monotone, so history codes only ever
    get COARSER, never clip), the already-resident codes are requantized at
    the grown scale, and the token's codes land at its offset. At
    ``offset == 0`` the scale RESETS to the fresh token's instead: the page
    was just (re)allocated, and inheriting the previous tenant's stale
    amax would poison this sequence's precision for the page's lifetime.
    Junk routed to null page 0 (frontier at/past mapped depth, duplicate
    rows) also writes ``scales[0]`` — never read, like the page itself.

    Shared (prefix-indexed) pages are never requantized here for the same
    reason :func:`_paged_cache_write` needs no guard: writes resolve only
    to pages private to the row by scheduler invariant.
    """
    lead = () if layer is None else (layer,)
    pt = pages.shape[len(lead) + 1 + axis]
    p_max = block_tables.shape[1]
    s = new.shape[1 + axis]
    newf = new.astype(jnp.float32)
    for j in range(s):
        pos = cache_len + j
        logical = jnp.minimum(pos // pt, p_max - 1)
        phys = jnp.take_along_axis(block_tables, logical[:, None],
                                   axis=1)[:, 0]
        phys = jnp.where(pos < p_max * pt, phys, 0)
        off = pos % pt
        tok = newf[:, j] if axis == 0 else newf[:, :, j]
        fresh = jnp.max(jnp.abs(tok),
                        axis=tuple(range(1, tok.ndim))) / INT8_QMAX
        at = lead + (phys,)
        old = scales[at]
        scl = jnp.where(off == 0, fresh, jnp.maximum(old, fresh))
        safe = jnp.where(scl > 0, scl, 1.0)
        page_shape_ones = (1,) * (pages.ndim - 1 - len(lead))
        page_f = (pages[at].astype(jnp.float32)
                  * old.reshape((-1,) + page_shape_ones))
        safe_b = safe.reshape((-1,) + page_shape_ones)
        page_new = jnp.clip(jnp.round(page_f / safe_b), -INT8_QMAX,
                            INT8_QMAX)
        tok_codes = jnp.clip(
            jnp.round(tok / safe.reshape((-1,) + (1,) * (tok.ndim - 1))),
            -INT8_QMAX, INT8_QMAX)
        iota = jax.lax.broadcasted_iota(jnp.int32, page_new.shape, 1 + axis)
        off_b = off.reshape((-1,) + page_shape_ones)
        page_new = jnp.where(iota == off_b,
                             jnp.expand_dims(tok_codes, 1 + axis), page_new)
        pages = pages.at[at].set(page_new.astype(pages.dtype))
        scales = scales.at[at].set(scl)
    return pages, scales


# ---------------------------------------------------------------------- GQA

def init_gqa(cfg: ModelConfig, key) -> Dict:
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    p = {
        "wq": jax.random.normal(ks[0], (d, hq * hd), jnp.float32) * s,
        "wk": jax.random.normal(ks[1], (d, hkv * hd), jnp.float32) * s,
        "wv": jax.random.normal(ks[2], (d, hkv * hd), jnp.float32) * s,
        "wo": jax.random.normal(ks[3], (hq * hd, d), jnp.float32)
        * (1.0 / math.sqrt(hq * hd)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * hd,), jnp.float32)
        p["bk"] = jnp.zeros((hkv * hd,), jnp.float32)
        p["bv"] = jnp.zeros((hkv * hd,), jnp.float32)
    return p


def init_gqa_cache(cfg: ModelConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16) -> Dict:
    shape = (batch, cfg.n_kv_heads, max_len, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_gqa_pages(cfg: ModelConfig, n_pages: int, page_tokens: int,
                   dtype=jnp.bfloat16, quant_scales: bool = False) -> Dict:
    """Flat page pool replacing the per-slot slab (page 0 = null page).

    With ``quant_scales`` (the int8 tier codec) each page also carries one
    f32 amax scale per leaf, stored as sibling ``*_scale`` arrays so they
    travel through every tier copy / park blob alongside their codes."""
    shape = (n_pages, cfg.n_kv_heads, page_tokens, cfg.head_dim)
    out = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if quant_scales:
        out["k_scale"] = jnp.zeros((n_pages,), jnp.float32)
        out["v_scale"] = jnp.zeros((n_pages,), jnp.float32)
    return out


def gqa_attention(p: Dict, x: jax.Array, *, cfg: ModelConfig,
                  kind: LayerKind,
                  positions: jax.Array,
                  cache: Optional[Dict] = None,
                  cache_len: Optional[jax.Array] = None,
                  positions3: Optional[jax.Array] = None,
                  cross_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
                  causal: bool = True,
                  plan: Optional[tiling.AttentionPlan] = None,
                  block_tables: Optional[jax.Array] = None,
                  layer: Optional[jax.Array] = None,
                  ) -> Tuple[jax.Array, Optional[Dict]]:
    """x: (B, S, d). Returns (out, updated_cache).

    Modes: training/prefill (cache=None, full seq); decode (cache given,
    S is the new-token count, cache_len the filled prefix length);
    cross-attention (cross_kv given: precomputed encoder K/V, no cache write).
    With ``block_tables`` the cache is the paged page pool instead of a
    per-slot slab: write + attention both walk the table (S == 1 for
    ordinary decode; S == k+1 for a speculative verify chunk). The pool's
    leaves are then stacked over the layer group's repetitions and read
    and written at index ``layer`` where they lie.
    """
    b, s, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    q = linear(x, p["wq"], p.get("bq"))
    q = q.reshape(b, s, hq, hd).transpose(0, 2, 1, 3)
    if cross_kv is None:
        k = linear(x, p["wk"], p.get("bk")).reshape(b, s, hkv, hd).transpose(0, 2, 1, 3)
        v = linear(x, p["wv"], p.get("bv")).reshape(b, s, hkv, hd).transpose(0, 2, 1, 3)
        if positions3 is not None and cfg.mrope:
            q = layers.apply_mrope(q, positions3, cfg.rope_theta, cfg.mrope_sections)
            k = layers.apply_mrope(k, positions3, cfg.rope_theta, cfg.mrope_sections)
        else:
            q = layers.apply_rope(q, positions, cfg.rope_theta)
            k = layers.apply_rope(k, positions, cfg.rope_theta)
    else:
        k, v = cross_kv

    if cache is not None and block_tables is not None:
        # paged two-tier pool: block-table write, page-walk attention. An
        # int8 pool (sibling ``*_scale`` leaves present) takes the
        # scale-aware write and hands the scales to the dequant-on-gather
        # attention; an fp8 pool needs neither — the plain write's astype
        # is the encode and the gather's upcast is the decode.
        k_scales = v_scales = None
        if "k_scale" in cache:
            k_pages, k_scales = _paged_cache_write_q(
                cache["k"], cache["k_scale"], k, cache_len, block_tables,
                axis=1, layer=layer)
            v_pages, v_scales = _paged_cache_write_q(
                cache["v"], cache["v_scale"], v, cache_len, block_tables,
                axis=1, layer=layer)
            new_cache = {"k": k_pages, "v": v_pages,
                         "k_scale": k_scales, "v_scale": v_scales}
        else:
            k_pages = _paged_cache_write(cache["k"], k, cache_len,
                                         block_tables, axis=1, layer=layer)
            v_pages = _paged_cache_write(cache["v"], v, cache_len,
                                         block_tables, axis=1, layer=layer)
            new_cache = {"k": k_pages, "v": v_pages}
        lead = (None,) * (k_pages.ndim - 4)     # the stacked pool's layer axis
        if heads_divide(hkv):
            # head-axis page placement: each mesh shard holds the page slice
            # its own KV heads read (q heads follow by GQA grouping), so the
            # page walk is shard-local — softmax/PV reduce over the seq dim,
            # which never crosses shards, making this bit-exact with the
            # replicated layout. Per-shard pool bytes drop by the model-axis
            # size; the geometry prices against the scaled aggregate
            # (DESIGN.md §Sharded serving).
            q = shard(q, BATCH, MODEL_AXIS, None, None)
            k_pages = shard(k_pages, *lead, None, MODEL_AXIS, None, None)
            v_pages = shard(v_pages, *lead, None, MODEL_AXIS, None, None)
        else:
            # heads don't divide: the page axis takes the seq shards' role
            # (pages spread over `model`); q replicates exactly as in the
            # dense pooled-decode layout.
            q = shard(q, BATCH, None, None, None)
            k_pages = shard(k_pages, *lead, MODEL_AXIS, None, None, None)
            v_pages = shard(v_pages, *lead, MODEL_AXIS, None, None, None)
        out = paged_decode_attention(q, k_pages, v_pages, block_tables,
                                     cache_len, layer=layer,
                                     window=kind.window,
                                     causal=causal, k_scale=k_scales,
                                     v_scale=v_scales)
        out = out.transpose(0, 2, 1, 3).reshape(b, s, hq * hd)
        return shard(linear(out, p["wo"]), BATCH, None, None), new_cache

    new_cache = None
    q_offset = 0
    if cache is not None:
        # functional cache append at cache_len
        k_all = _cache_write(cache["k"], k, cache_len, axis=2)
        v_all = _cache_write(cache["v"], v, cache_len, axis=2)
        new_cache = {"k": k_all, "v": v_all}
        k, v = k_all, v_all
        q_offset = cache_len

    if cache is not None and heads_divide(hkv):
        # dense slab, heads divide the model axis: same head-axis placement
        # as the paged pool above, so dense and paged serve paths (and the
        # one-shot reference) stay bit-identical at any mesh size — a
        # seq-sharded softmax here would reassociate the reduction
        # (partial-stat psums) and break the equivalence matrix.
        q = shard(q, BATCH, MODEL_AXIS, None, None)
        k = shard(k, BATCH, MODEL_AXIS, None, None)
        v = shard(v, BATCH, MODEL_AXIS, None, None)
    elif cache is not None:
        # pooled KV: sequence dim spread over the model axis (flash-decoding).
        # q heads REPLICATE here — a head-sharded q against seq-sharded KV
        # forces GSPMD into replicate-and-reslice copies of the whole cache
        # per layer (§Perf, deepseek/h1); with q replicated, the softmax and
        # PV contractions reduce over the seq shards with small stat psums.
        q = shard(q, BATCH, None, None, None)
        k = shard(k, BATCH, None, "model", None)
        v = shard(v, BATCH, None, "model", None)
    else:
        q = shard(q, BATCH, "model", None, None)
        k = shard(k, BATCH, "model", None, None)
        v = shard(v, BATCH, "model", None, None)

    if cache is not None and isinstance(q_offset, jax.Array) and (
            s == 1 or q_offset.ndim == 1):
        # decode with traced offset: direct masked attention over the cache.
        # s > 1 with per-slot offsets is the speculative verify chunk — the
        # same oracle scores every candidate with causal-within-chunk masks
        # at qpos = cache_len + arange(s) (DESIGN.md §Speculative decoding)
        out = _decode_attention(q, k, v, q_offset, window=kind.window,
                                causal=causal)
    else:
        # prefill — including multi-token chunks resuming at a TRACED
        # cursor (chunked prefill): the offset only shifts the causal mask,
        # so this is the same blockwise math as a static-offset prefill
        out = ops.attention(q, k, v, causal=causal and cross_kv is None,
                            window=kind.window,
                            q_offset=(q_offset
                                      if isinstance(q_offset, jax.Array)
                                      else int(q_offset)),
                            plan=plan)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, hq * hd)
    out = linear(out, p["wo"])
    return shard(out, BATCH, None, None), new_cache


# The masked decode-attention oracle lives in kernels/paged_attention so the
# paged page-walk path can share its exact math (paged == dense bit-exact);
# the dense slab path below calls the same function.
_decode_attention = decode_attention_masked


# ---------------------------------------------------------------------- MLA

def init_mla(cfg: ModelConfig, key) -> Dict:
    d, h = cfg.d_model, cfg.n_heads
    nope, rope_d, vdim = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    qd = nope + rope_d
    ks = jax.random.split(key, 6)
    p = {}
    if cfg.q_lora_rank:
        p["wq_a"] = jax.random.normal(ks[0], (d, cfg.q_lora_rank), jnp.float32) / math.sqrt(d)
        p["q_norm"] = layers.init_rmsnorm(cfg.q_lora_rank)
        p["wq_b"] = jax.random.normal(ks[1], (cfg.q_lora_rank, h * qd), jnp.float32) / math.sqrt(cfg.q_lora_rank)
    else:
        p["wq_b"] = jax.random.normal(ks[1], (d, h * qd), jnp.float32) / math.sqrt(d)
    p["wkv_a"] = jax.random.normal(ks[2], (d, cfg.kv_lora_rank + rope_d), jnp.float32) / math.sqrt(d)
    p["kv_norm"] = layers.init_rmsnorm(cfg.kv_lora_rank)
    p["wkv_b"] = jax.random.normal(ks[3], (cfg.kv_lora_rank, h * (nope + vdim)), jnp.float32) / math.sqrt(cfg.kv_lora_rank)
    p["wo"] = jax.random.normal(ks[4], (h * vdim, d), jnp.float32) / math.sqrt(h * vdim)
    return p


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16) -> Dict:
    return {
        "ckv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "krope": jnp.zeros((batch, max_len, cfg.qk_rope_head_dim), dtype),
    }


def init_mla_pages(cfg: ModelConfig, n_pages: int, page_tokens: int,
                   dtype=jnp.bfloat16, quant_scales: bool = False) -> Dict:
    """Paged latent pool: pages of the 576-dim latent, not per-head K/V."""
    out = {
        "ckv": jnp.zeros((n_pages, page_tokens, cfg.kv_lora_rank), dtype),
        "krope": jnp.zeros((n_pages, page_tokens, cfg.qk_rope_head_dim),
                           dtype),
    }
    if quant_scales:
        out["ckv_scale"] = jnp.zeros((n_pages,), jnp.float32)
        out["krope_scale"] = jnp.zeros((n_pages,), jnp.float32)
    return out


def mla_attention(p: Dict, x: jax.Array, *, cfg: ModelConfig,
                  kind: LayerKind,
                  positions: jax.Array,
                  cache: Optional[Dict] = None,
                  cache_len: Optional[jax.Array] = None,
                  plan: Optional[tiling.AttentionPlan] = None,
                  block_tables: Optional[jax.Array] = None,
                  layer: Optional[jax.Array] = None,
                  **_unused) -> Tuple[jax.Array, Optional[Dict]]:
    """Multi-head latent attention. Cache stores only the 576-dim latent —
    the paper's 'more capacity in the same footprint', algorithmically."""
    b, s, d = x.shape
    h = cfg.n_heads
    nope, rope_d, vdim = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim

    if cfg.q_lora_rank:
        q = linear(layers.rmsnorm(p["q_norm"], linear(x, p["wq_a"])), p["wq_b"])
    else:
        q = linear(x, p["wq_b"])
    q = q.reshape(b, s, h, nope + rope_d).transpose(0, 2, 1, 3)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = layers.apply_rope(q_rope, positions, cfg.rope_theta)

    kv_a = linear(x, p["wkv_a"])                        # (B,S,kv_lora+rope)
    ckv = layers.rmsnorm(p["kv_norm"], kv_a[..., :cfg.kv_lora_rank])
    k_rope = kv_a[..., cfg.kv_lora_rank:]               # single shared head
    k_rope = layers.apply_rope(k_rope[:, None], positions, cfg.rope_theta)[:, 0]

    new_cache = None
    q_offset = 0
    if cache is not None and block_tables is not None:
        # paged latent pool: block-table write, then gather back the same
        # contiguous per-slot view the dense slab holds — the absorbed
        # decode below is untouched and bit-exact with the dense path. A
        # stacked pool is written and gathered at index ``layer``. An
        # int8 latent pool dequantizes on the gather (codes × per-page
        # scale); fp8 upcasts in the gather's astype. Beyond-frontier
        # positions hold junk either way — masked exactly like stale K/V.
        if "ckv_scale" in cache:
            ckv_pages, ckv_scales = _paged_cache_write_q(
                cache["ckv"], cache["ckv_scale"], ckv, cache_len,
                block_tables, axis=0, layer=layer)
            krope_pages, krope_scales = _paged_cache_write_q(
                cache["krope"], cache["krope_scale"], k_rope, cache_len,
                block_tables, axis=0, layer=layer)
            new_cache = {"ckv": ckv_pages, "krope": krope_pages,
                         "ckv_scale": ckv_scales,
                         "krope_scale": krope_scales}
            at = (block_tables,) if layer is None else (layer, block_tables)
            pt = ckv_pages.shape[-2]
            ckv = (gather_kv_pages(ckv_pages, block_tables, seq_axis=0,
                                   layer=layer).astype(jnp.float32)
                   * jnp.repeat(ckv_scales[at], pt, axis=1)[:, :, None])
            k_rope = (gather_kv_pages(krope_pages, block_tables, seq_axis=0,
                                      layer=layer).astype(jnp.float32)
                      * jnp.repeat(krope_scales[at], pt, axis=1)[:, :, None])
        else:
            ckv_pages = _paged_cache_write(cache["ckv"], ckv, cache_len,
                                           block_tables, axis=0, layer=layer)
            krope_pages = _paged_cache_write(cache["krope"], k_rope,
                                             cache_len, block_tables, axis=0,
                                             layer=layer)
            new_cache = {"ckv": ckv_pages, "krope": krope_pages}
            ckv = gather_kv_pages(ckv_pages, block_tables, seq_axis=0,
                                  layer=layer)
            k_rope = gather_kv_pages(krope_pages, block_tables, seq_axis=0,
                                     layer=layer)
            if ckv.dtype not in (jnp.bfloat16, jnp.float16, jnp.float32):
                ckv = ckv.astype(jnp.float32)       # fp8 tier: decode=upcast
                k_rope = k_rope.astype(jnp.float32)
        q_offset = cache_len
    elif cache is not None:
        ckv = _cache_write(cache["ckv"], ckv, cache_len, axis=1)
        k_rope = _cache_write(cache["krope"], k_rope, cache_len, axis=1)
        new_cache = {"ckv": ckv, "krope": k_rope}
        q_offset = cache_len

    scale = (nope + rope_d) ** -0.5

    if cache is not None and isinstance(q_offset, jax.Array) and (
            s == 1 or q_offset.ndim == 1):
        # ---- ABSORBED (latent-space) decode: never materialize per-head
        # K/V. q_nope is folded through wkv_b's K half so scores/values are
        # computed directly against the 576-dim latent cache — O(T*(l+r))
        # per query instead of O(T*h*(d_k+d_v)) decompression, and the
        # seq-sharded latent never reshards (§Perf, deepseek/h1).
        if heads_divide(h):
            # heads divide: replicate the latent (it has no head axis to
            # place) and shard the folded-q heads instead — each shard scores
            # its own heads against the whole local latent, bit-exact with
            # the replicated layout. MLA pool capacity therefore does NOT
            # scale with model shards (repro.serve.scheduler.kv_shards).
            ckv = shard(ckv, BATCH, None, None)
            k_rope = shard(k_rope, BATCH, None, None)
        else:
            ckv = shard(ckv, BATCH, "model", None)      # pooled latent
            k_rope = shard(k_rope, BATCH, "model", None)
        w = cast(p["wkv_b"]).reshape(cfg.kv_lora_rank, h, nope + vdim)
        wk, wv = w[..., :nope], w[..., nope:]           # (l, h, n) / (l, h, v)
        qf = q_nope.astype(jnp.float32)                 # (B, H, S, n)
        if heads_divide(h):
            qf = shard(qf, BATCH, MODEL_AXIS, None, None)
        q_lat = jnp.einsum("bhsn,lhn->bhsl", qf, wk.astype(jnp.float32))
        ckv_f = ckv.astype(jnp.float32)                 # (B, T, l)
        kr_f = k_rope.astype(jnp.float32)               # (B, T, r)
        scores = (jnp.einsum("bhsl,btl->bhst", q_lat, ckv_f)
                  + jnp.einsum("bhsr,btr->bhst",
                               q_rope.astype(jnp.float32), kr_f)) * scale
        t_pos = jnp.arange(ckv.shape[1])[None, :]
        if q_offset.ndim == 1:                          # per-slot lengths (B,)
            q_offset = q_offset.reshape(b, 1, 1, 1)
        q_pos = q_offset + jnp.arange(s)[:, None]
        mask = t_pos <= q_pos                           # causal + written
        scores = jnp.where(mask, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        o_lat = jnp.einsum("bhst,btl->bhsl", probs, ckv_f)
        out = jnp.einsum("bhsl,lhv->bhsv", o_lat, wv.astype(jnp.float32))
        out = out.astype(x.dtype)
    else:
        # prefill/train: decompress + flash attention (compute-optimal for
        # long query blocks; the latent trick only wins at small s). A
        # multi-token chunk resuming at a TRACED cursor lands here too —
        # the same decompression an unchunked (static-offset) prefill does.
        ckv = shard(ckv, BATCH, None, None)
        kv = linear(ckv, p["wkv_b"]).reshape(*ckv.shape[:2], h, nope + vdim)
        k_nope = kv[..., :nope].transpose(0, 2, 1, 3)   # (B,H,Skv,nope)
        v = kv[..., nope:].transpose(0, 2, 1, 3)        # (B,H,Skv,v)
        k_rope_b = jnp.broadcast_to(k_rope[:, None], (b, h, *k_rope.shape[1:]))
        k = jnp.concatenate([k_nope, k_rope_b], axis=-1)
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = ops.attention(q_full, k, v, causal=True, window=kind.window,
                            scale=scale,
                            q_offset=(q_offset
                                      if isinstance(q_offset, jax.Array)
                                      else int(q_offset)),
                            plan=plan)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, h * vdim)
    return linear(out, p["wo"]), new_cache


INIT = {"gqa": init_gqa, "mla": init_mla}
APPLY = {"gqa": gqa_attention, "mla": mla_attention}
