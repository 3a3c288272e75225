"""Paged decode attention: walk a block table over a flat page pool.

The paged two-tier KV pool (DESIGN.md §Paged two-tier pool) stores KV as a
flat array of fixed-size pages — ``(n_pages, ..., page_tokens, ...)`` — and
each sequence slot maps logical page indices to physical pages through an
int32 block table. This module owns the decode-attention math over that
layout:

  * :func:`decode_attention_masked` — the dense masked decode-attention
    oracle (GQA without materializing repeated K/V, traced valid-prefix
    masking). This is THE reference: the dense slot-slab serving path calls
    it directly, and the paged path reduces to it after a gather, so
    paged == dense is bit-exact by construction.
  * :func:`gather_kv_pages` — block-table gather: physical pages back into
    a per-slot contiguous view.
  * :func:`paged_decode_attention` — the public entry. On CPU (and under
    ``impl="ref"``) it gathers and calls the oracle; on TPU it runs the
    Pallas page walk, :func:`paged_flash_decode`: grid over (slot, block
    of :func:`walk_pages_per_block` pages), the pools left in HBM and the
    block table scalar-prefetched, so each cell DMAs exactly the pages of
    its slot that the frontier (and window) leave visible, all of a page's
    KV heads in one copy, double-buffered so the next block's pages land
    while this block's math runs — the two-tier pool's analogue of MemPool
    fetching only the banks a tile maps to. Blocks wholly outside the
    visible span start no copy and do no arithmetic, so the walk's cost
    follows the live pages, not the slot depth.

Page ALIASING is invisible to everything here: attention only ever reads
through a slot's block table, so two slots mapping the same physical page
(ref-counted prefix sharing — DESIGN.md §Prefix sharing & copy-on-write)
each see it as ordinary positions of their own contiguous view, and the
gather/page-walk math is unchanged. The aliasing contract lives entirely
at the WRITE edge, upstream of this module: shared pages are full prompt
pages strictly behind every reader's ``cache_len`` frontier, and the one
page a cache-hit admission both matches and writes (the partial frontier
page of a page-aligned full match) is copied into a private page at
admission — so the per-token append in ``attention._paged_cache_write``
can never land in a page another slot reads.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hw_profiles import TPU_V5E
from repro.distributed.sharding import (BATCH, MODEL_AXIS, heads_divide,
                                       kernel_heads_shard, over_heads, shard)
from repro.kernels import ops

_NEG_INF = float("-inf")
_STATS_LANES = 128   # stats scratch is (group, 128) for TPU lane alignment
#: the page walk's double-buffered K and V blocks may take half the scoped
#: VMEM a kernel gets; the rest is its f32 working set
_WALK_VMEM_BYTES = TPU_V5E.vmem_limit_bytes // 2

INT8_QMAX = 127.0    # symmetric int8: codes in [-127, 127], -128 unused


# ------------------------------------------------------------ quantization
#
# Per-page symmetric int8 (DESIGN.md §Tiered KV compression): each page
# carries ONE f32 scale per leaf (amax / 127 over everything in the page),
# stored in a sibling `<leaf>_scale` array of shape (n_pages,). fp8-e4m3
# needs no scales — KV values live inside e4m3's dynamic range and the
# cast/uncast is a plain astype. fp16 (bf16 storage) is the identity.


def quantize_page_int8(x: jax.Array, axes) -> tuple:
    """Quantize ``x`` to symmetric int8 with one scale per un-reduced index.

    ``axes`` are the reduced (per-page) axes: the scale is
    ``amax(|x|, axes) / 127`` and the codes ``round(x / scale)`` clipped to
    [-127, 127]. An all-zero page gets scale 0 and all-zero codes — the
    dequant ``codes * 0`` round-trips it exactly. Returns ``(codes int8,
    scales f32)`` with ``scales.shape == x.shape`` minus ``axes``.
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=axes)
    scales = amax / INT8_QMAX
    safe = jnp.where(scales > 0, scales, 1.0)
    expand = list(axes) if isinstance(axes, (tuple, list)) else [axes]
    safe_b = jnp.expand_dims(safe, expand)
    codes = jnp.clip(jnp.round(xf / safe_b), -INT8_QMAX, INT8_QMAX)
    return codes.astype(jnp.int8), scales


def dequantize_page_int8(codes: jax.Array, scales: jax.Array,
                         axes) -> jax.Array:
    """Inverse of :func:`quantize_page_int8` (f32 out); ``axes`` are the
    page axes the scales were reduced over."""
    expand = list(axes) if isinstance(axes, (tuple, list)) else [axes]
    return codes.astype(jnp.float32) * jnp.expand_dims(scales, expand)


# ------------------------------------------------------------------ oracle


def decode_attention_masked(q, k, v, cache_len, *, window=None, causal=True):
    """Masked attention with a traced valid-prefix length (decode path).

    GQA WITHOUT materializing repeated K/V: q is viewed as
    (B, Hkv, group, S, D) and contracted against the (B, Hkv, T, D) cache —
    a jnp.repeat here lowers to broadcast+reshape that merges the head dims,
    which breaks GSPMD's seq-sharding propagation and all-gathers the whole
    pooled cache per layer (§Perf, decode/h3).

    ``cache_len`` is a scalar or a per-row ``(B,)`` vector (slot pool: rows
    at different fill depths decode in one batched step). Positions at or
    beyond a row's frontier — including stale K/V left over from a padded
    prefill or a previous occupant of the slot — are masked out, so a slot
    row never attends across its own reuse boundary."""
    b, hq, s, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    group = hq // hkv
    scale = d ** -0.5
    qg = q.reshape(b, hkv, group, s, d)
    logits = jnp.einsum("bhgsd,bhtd->bhgst", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if isinstance(cache_len, jax.Array) and cache_len.ndim == 1:
        # (B,1,1,1,1): broadcasts against logits' (B,Hkv,group,S,T)
        cache_len = cache_len.reshape(b, 1, 1, 1, 1)
    qpos = cache_len + jnp.arange(s)[:, None]
    tpos = jnp.arange(skv)[None, :]
    mask = tpos < cache_len + s            # written region only
    if causal:
        mask = mask & (tpos <= qpos)
    if window is not None:
        mask = mask & (tpos > qpos - window)
    logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgst,bhtd->bhgsd",
                     probs.astype(jnp.float32), v.astype(jnp.float32))
    return out.reshape(b, hq, s, d).astype(q.dtype)


# ------------------------------------------------------------------ gather


def gather_kv_pages(pages: jax.Array, block_tables: jax.Array, *,
                    seq_axis: int, layer=None) -> jax.Array:
    """Walk the block table: physical pages -> per-slot contiguous KV.

    ``pages`` is ``(n_pages, *page_shape)`` with ``page_shape[seq_axis] ==
    page_tokens``, or the stacked pool ``(L, n_pages, *page_shape)`` read
    at index ``layer``; ``block_tables`` is ``(B, P)`` int32. Returns
    ``(B, *page_shape)`` with the seq axis widened to ``P * page_tokens``.
    Unmapped entries (null page 0) gather junk that the caller's frontier
    mask must hide — exactly like stale rows in the dense slab.
    """
    lead = () if layer is None else (layer,)
    gathered = pages[lead + (block_tables,)]       # (B, P, *page_shape)
    gathered = jnp.moveaxis(gathered, 1, seq_axis + 1)
    shape = list(gathered.shape)
    merged = shape[:seq_axis + 1] + [shape[seq_axis + 1] * shape[seq_axis + 2]]
    return gathered.reshape(merged + shape[seq_axis + 3:])


# ------------------------------------------------------------ Pallas kernel

#: tokens one grid step of the page walk covers. On a TPU v5e at
#: qwen2.5-3b's widths (32 slots of 256 pages of 16 tokens), one layer's
#: walk took 0.278, 0.236, 0.235 and 0.260 ms at 256, 512, 1024 and 2048
#: tokens a block with chat-like frontiers, 0.387, 0.300, 0.285 and 0.301
#: ms with code-like ones: fewer, larger steps stop paying once a slot's
#: last block is mostly masked positions
WALK_BLOCK_TOKENS = 512


def walk_pages_per_block(page_tokens: int, p_max: int) -> int:
    """Pages one grid step of :func:`paged_flash_decode` fetches: about
    :data:`WALK_BLOCK_TOKENS` tokens, at most a slot's ``p_max`` pages."""
    return max(1, min(p_max, WALK_BLOCK_TOKENS // page_tokens))


def _paged_decode_kernel(bt_ref, len_ref, layer_ref, q_ref, k_hbm, v_hbm,
                         o_ref, k_buf, v_buf, sems, m_ref, l_ref, acc_ref, *,
                         page_tokens: int, ppb: int, p_max: int,
                         n_blocks: int, scale: float, window: int | None):
    """One (slot, block of ``ppb`` logical pages) cell of the page walk
    over layer ``layer_ref[0]`` of the stacked pool.

    The slot's visible blocks run from ``first`` (the window's start, else
    0) to ``last`` (the frontier's block). Each visible block's visible
    pages are DMA'd from HBM into one half of a double buffer, the next
    block's pages are started before this block's math, and cells outside
    ``[first, last]`` start no copy and do no arithmetic."""
    ib, j = pl.program_id(0), pl.program_id(1)
    layer = layer_ref[0]
    hkv = q_ref.shape[1]
    bk = ppb * page_tokens
    frontier = len_ref[ib]                    # this slot's filled prefix
    lo_tok = 0 if window is None else jnp.maximum(frontier - window + 1, 0)
    first = lo_tok // bk
    last = jnp.minimum(frontier // bk, n_blocks - 1)

    def copies(blk, buf, i):
        """Whether page ``i`` of block ``blk`` is visible, and its K and V
        copies into half ``buf`` of the double buffer."""
        p = blk * ppb + i
        vis = (p < p_max) & (p * page_tokens <= frontier)
        if window is not None:
            vis &= p * page_tokens + page_tokens > lo_tok
        page = bt_ref[ib * n_blocks * ppb + p]
        return vis, (pltpu.make_async_copy(k_hbm.at[layer, page],
                                           k_buf.at[buf, i], sems.at[0, buf]),
                     pltpu.make_async_copy(v_hbm.at[layer, page],
                                           v_buf.at[buf, i], sems.at[1, buf]))

    def each_page(blk, buf, act):
        """``act`` on the K and V copies of each visible page of ``blk``.
        The loop is unrolled when lowered, not in Python: its body is
        traced once, which takes seconds off tracing a decode chunk."""
        def body(i, carry):
            vis, dmas = copies(blk, buf, i)

            @pl.when(vis)
            def _():
                for dma in dmas:
                    act(dma)
            return carry

        jax.lax.fori_loop(0, ppb, body, 0, unroll=True)

    def start(blk):
        each_page(blk, blk % 2, lambda dma: dma.start())

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j == first)
    def _prime():
        start(j)

    @pl.when((j >= first) & (j < last))
    def _prefetch():
        start(j + 1)

    @pl.when((j >= first) & (j <= last))
    def _update():
        buf = j % 2
        each_page(j, buf, lambda dma: dma.wait())

        def visible(shape, axis):
            pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, shape, axis)
            vis = (pos <= frontier) & (pos < p_max * page_tokens)
            if window is not None:
                vis &= pos >= lo_tok
            return vis

        mask, rows = visible((1, bk), 1), visible((bk, 1), 0)
        for h in range(hkv):
            q = q_ref[0, h].astype(jnp.float32)                 # (group, d)
            k = k_buf[buf, :, h].astype(jnp.float32).reshape(bk, -1)
            v = v_buf[buf, :, h].astype(jnp.float32).reshape(bk, -1)
            # pages left unfetched hold whatever the buffer held: zero
            # their rows so masked scores never meet non-finite values
            v = jnp.where(rows, v, 0.0)
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
            s = jnp.where(mask, s, _NEG_INF)
            m_prev = m_ref[h, :, :1]
            l_prev = l_ref[h, :, :1]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.where(m_prev > _NEG_INF, jnp.exp(m_prev - m_new),
                              0.0)
            p = jnp.where(s > _NEG_INF, jnp.exp(s - m_new), 0.0)
            l_new = alpha * l_prev + p.sum(axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(j == n_blocks - 1)
    def _flush():
        for h in range(hkv):
            l = l_ref[h, :, :1]
            o_ref[0, h] = (acc_ref[h] / jnp.maximum(l, 1e-30)
                           ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_flash_decode(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                       block_tables: jax.Array, cache_len: jax.Array,
                       layer: jax.Array | int, *,
                       window: int | None = None,
                       interpret: bool = False) -> jax.Array:
    """Pallas page-walk decode attention.

    q: (B, Hq, 1, D); k_pages/v_pages: the stacked pool (L, n_pages, Hkv,
    page_tokens, D), read at index ``layer``; block_tables: (B, P) int32;
    cache_len: (B,) int32. The grid is (slot, block of
    :func:`walk_pages_per_block` pages); the pools stay in HBM, where the
    decode chunk's layer scan keeps them, and each cell DMAs, through the
    scalar-prefetched block table and layer index, only the pages of its
    slot that the frontier (and window) leave visible, all KV heads of a
    page in one copy. The table is padded with null page 0 to whole
    blocks; pages past ``P`` are never fetched.
    """
    b, hq, s, d = q.shape
    assert s == 1, "paged decode attention is single-token"
    hkv, page_tokens, dv = (k_pages.shape[2], k_pages.shape[3],
                            v_pages.shape[-1])
    group = hq // hkv
    p_max = block_tables.shape[1]
    ppb = walk_pages_per_block(page_tokens, p_max)
    n_blocks = pl.cdiv(p_max, ppb)
    page_bytes = lambda x: math.prod(x.shape[2:]) * x.dtype.itemsize
    buffers = 2 * ppb * (page_bytes(k_pages) + page_bytes(v_pages))
    if buffers > _WALK_VMEM_BYTES:
        raise ValueError(
            f"page walk: {ppb} pages per block need {buffers} bytes of "
            f"double-buffered VMEM, over {_WALK_VMEM_BYTES}")
    qg = q.reshape(b, hkv, group, d)
    tables = jnp.pad(block_tables.astype(jnp.int32),
                     ((0, 0), (0, n_blocks * ppb - p_max))).reshape(-1)

    kernel = functools.partial(
        _paged_decode_kernel, page_tokens=page_tokens, ppb=ppb, p_max=p_max,
        n_blocks=n_blocks, scale=d ** -0.5, window=window)

    per_slot = lambda ib, j, *prefetched: (ib, 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_blocks),
        in_specs=[
            pl.BlockSpec((1, hkv, group, d), per_slot),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, hkv, group, dv), per_slot),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, hkv, page_tokens, d), k_pages.dtype),
            pltpu.VMEM((2, ppb, hkv, page_tokens, dv), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((hkv, group, _STATS_LANES), jnp.float32),
            pltpu.VMEM((hkv, group, _STATS_LANES), jnp.float32),
            pltpu.VMEM((hkv, group, dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tables, cache_len.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), qg, k_pages, v_pages)
    return out.reshape(b, hq, 1, dv)


# ------------------------------------------------------------------ entry


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_tables: jax.Array,
                           cache_len: jax.Array, *,
                           layer: jax.Array | int | None = None,
                           window: int | None = None,
                           causal: bool = True,
                           impl: str = "auto",
                           k_scale: jax.Array | None = None,
                           v_scale: jax.Array | None = None) -> jax.Array:
    """Decode attention over the paged pool; dense math is the oracle.

    The pools are one layer's ``(n_pages, Hkv, page_tokens, D)``, or, with
    ``layer``, the stacked ``(L, n_pages, Hkv, page_tokens, D)`` the decode
    chunk's layer scan carries, read at index ``layer`` where they lie:
    the walk DMAs that layer's pages and the gather indexes them, so no
    layer is sliced out. Int8 scales follow their pages: ``(n_pages,)`` or
    ``(L, n_pages)``.

    ``impl="auto"`` walks pages with the Pallas kernel on TPU and takes the
    gather + :func:`decode_attention_masked` path elsewhere — the latter is
    bit-identical to the dense slot-slab path, which serving relies on for
    paged == dense equivalence (tolerances only enter with the Pallas
    kernel's online softmax, validated in tests/test_kernels.py).

    ``q`` may carry more than one query position per slot: the speculative
    verify chunk (DESIGN.md §Speculative decoding) scores k+1 candidate
    tokens at positions ``cache_len + j`` in one forward. The Pallas page
    walk is single-token, so ``impl="auto"`` routes multi-position queries
    through the gather + oracle path (whose masks already handle
    ``qpos = cache_len + arange(s)``); an explicit ``impl="pallas"`` still
    asserts.

    Quantized pools (DESIGN.md §Tiered KV compression): int8 pages carry
    per-page ``k_scale``/``v_scale`` vectors ``(n_pages,)`` and fp8-e4m3
    pages are detected by dtype; both dequantize AFTER the block-table
    gather (dequant-on-gather) and run the oracle — the page walk moves
    half the bytes, the math is unchanged. The Pallas kernel stays
    fp16-only for now, so quantized pools always take the gather path.
    """
    if layer is None:
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
    hkv = k_pages.shape[-3]
    on_tpu = ops.on_tpu()
    single = q.shape[2] == 1
    quantized = (k_scale is not None
                 or k_pages.dtype not in (jnp.bfloat16, jnp.float16,
                                          jnp.float32))
    if quantized and impl == "pallas":
        raise NotImplementedError(
            "the Pallas page walk reads fp16 pages; quantized pools "
            "dequantize on gather (impl='auto')")
    use_pallas = (impl == "pallas") or (
        impl == "auto" and on_tpu and single and not quantized
        and kernel_heads_shard(hkv))
    if use_pallas and causal:
        walk = functools.partial(paged_flash_decode, window=window,
                                 interpret=not on_tpu)
        return over_heads(walk, ("bh", "h", "h", "b", "b", ""))(
            q, k_pages, v_pages, block_tables, cache_len,
            jnp.asarray(layer, jnp.int32))
    k = gather_kv_pages(k_pages, block_tables, seq_axis=1, layer=layer)
    v = gather_kv_pages(v_pages, block_tables, seq_axis=1, layer=layer)
    if k_scale is not None:
        # per-page scalar scales -> per-token columns of the gathered view
        pt = k_pages.shape[3]
        ks = jnp.repeat(k_scale[layer, block_tables], pt, axis=1)  # (B, P*pt)
        vs = jnp.repeat(v_scale[layer, block_tables], pt, axis=1)
        k = k.astype(jnp.float32) * ks[:, None, :, None]
        v = v.astype(jnp.float32) * vs[:, None, :, None]
    elif quantized:
        k = k.astype(jnp.float32)
        v = v.astype(jnp.float32)
    if heads_divide(hkv):
        # pin the gathered per-slot view to the head shards that own the
        # pages: the block-table gather indexes the (replicated-looking)
        # page axis, and without the constraint GSPMD may resolve it by
        # all-gathering the head-sharded pool first.
        q = shard(q, BATCH, MODEL_AXIS, None, None)
        k = shard(k, BATCH, MODEL_AXIS, None, None)
        v = shard(v, BATCH, MODEL_AXIS, None, None)
    return decode_attention_masked(q, k, v, cache_len,
                                   window=window, causal=causal)
