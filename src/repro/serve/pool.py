"""Pool ownership for the serving engine: construction, tier copies, and
the page-handover primitive (DESIGN.md §Disaggregated serving).

The KV pool is ONE device-side address space — MemPool-3D's premise,
applied to serving: whatever engine role computes against it, the pages
live in the same flat layer-0/layer-1 arrays. This module owns everything
about that pool that is not a model forward:

  * :class:`PoolState` — the device arrays (moved here from
    ``serve/engine.py``; the engine re-exports it for compatibility).
  * :class:`PoolManager` — constructs empty pools (:meth:`init_pool` /
    :meth:`init_paged_pool`), executes the layer-0 <-> layer-1 tier
    copies planned by the scheduler (:meth:`exec_spill` /
    :meth:`exec_restore`), and tracks which engine *role* owns each
    slot when serving runs disaggregated.
  * :meth:`PoolManager.transfer_ownership` — the handover primitive. At
    a request's final prefill chunk, its slot moves from the prefill
    role to the decode role by flipping ONE host-side table entry: the
    slot's block-table row starts appearing in the decode role's
    uploaded table, and the prefill role stops issuing work for it. No
    KV bytes move — the pages were always in the shared pool; only the
    table row and cursor change hands (the invariant the equivalence
    matrix pins: a page row moves, bytes never copy).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.target import get_target
from repro.serve import scheduler as sched_mod

#: Engine role names (DESIGN.md §Disaggregated serving). The prefill role
#: runs admissions and prompt chunks; the decode role runs the batched
#: decode/verify forwards. A combined engine is both at once. Canonical
#: definitions live in the scheduler (routing is a scheduling decision).
PREFILL_ROLE = sched_mod.PREFILL_ROLE
DECODE_ROLE = sched_mod.DECODE_ROLE


# ----------------------------------------------------- device pool budget

#: How many copies of the layer-0 pool the costliest jitted step holds at
#: once. The steps donate the pool, so argument and output share buffers;
#: the decode chunk's scan over the stacked layers still builds the updated
#: caches beside the old ones (``memory_analysis()`` of the pooled decode
#: chunk at qwen2.5-3b widths on a v5e: temp = pool + 0.57 GB).
#: tests/test_chip_compile.py holds the compiled steps to this count.
POOL_STEP_COPIES = 2

#: Share of the bytes left beside the weights that the pool tiers take; the
#: rest is for the steps' activations.
POOL_FRACTION = 0.8


def pool_bytes_beside(params: Any, *, target=None) -> Tuple[int, int]:
    """Per-device ``(layer0_bytes, layer1_bytes)`` that fit beside
    ``params`` on the first device.

    The device's ``bytes_limit`` (the target's pool-level capacity where the
    backend reports none) less the bytes of ``params`` that live on it
    leaves ``free``. :data:`POOL_FRACTION` of it goes to the pool, split
    between the tiers in the scheduler's die-split ratio
    (``default_layer1_fraction``) after charging layer 0
    :data:`POOL_STEP_COPIES` times. Pass the result as
    ``derive_page_geometry(layer0_bytes=..., layer1_bytes=...)``: both are
    per-shard budgets, as that function expects."""
    target = target or get_target()
    dev = jax.devices()[0]
    limit = (dev.memory_stats() or {}).get("bytes_limit") or \
        sched_mod.pool_partition(target, fraction=1.0).capacity_bytes
    weights = sum(s.data.nbytes for leaf in jax.tree.leaves(params)
                  for s in leaf.addressable_shards if s.device == dev)
    free = int(limit) - weights
    if free <= 0:
        raise ValueError(f"the weights take {weights} B of the device's "
                         f"{limit} B; nothing is left for the KV pool")
    layer1_fraction = sched_mod.default_layer1_fraction(target)
    layer0 = int(POOL_FRACTION * free / (POOL_STEP_COPIES + layer1_fraction))
    return layer0, int(layer0 * layer1_fraction)


# ------------------------------------------------------------- tier codecs


@dataclasses.dataclass(frozen=True)
class TierCodec:
    """How one pool tier stores KV bytes (DESIGN.md §Tiered KV compression).

    ``scaled`` codecs (int8) carry one f32 scale per page per leaf in a
    sibling ``<leaf>_scale`` array; unscaled codecs are a plain dtype cast
    (fp8-e4m3) or the identity (fp16 — bf16 storage, bit-exact by
    construction, the reference every quantized tier is gated against).
    """

    name: str
    dtype: Any
    bytes_per_value: int
    scaled: bool


CODECS: Dict[str, TierCodec] = {
    "fp16": TierCodec("fp16", jnp.bfloat16, 2, False),
    "fp8": TierCodec("fp8", jnp.float8_e4m3fn, 1, False),
    "int8": TierCodec("int8", jnp.int8, 1, True),
}


def quant_policy(kv_quant: Optional[str]) -> Tuple[str, str]:
    """Map a ``--kv-quant`` knob to ``(layer0_codec, layer1_codec)``.

    The spill tier quantizes at least as hard as layer 0 — layer-1
    bandwidth is cheap (pages move once per preemption), capacity is not —
    so ``fp8`` spills as int8 while ``int8`` is already at the floor.
    """
    if kv_quant in (None, "none", "fp16"):
        return ("fp16", "fp16")
    if kv_quant == "fp8":
        return ("fp8", "int8")
    if kv_quant == "int8":
        return ("int8", "int8")
    raise ValueError(f"unknown kv quant codec {kv_quant!r} "
                     f"(choices: {', '.join(sorted(CODECS))})")


def _has_recurrent_state(cfg) -> bool:
    return any(kind.attn == "mamba"
               for group in cfg.layer_groups() for kind in group.pattern)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PoolState:
    """Device-side state of the KV slot pool (batch axis = slot index).

    ``block_tables`` is ``None`` for the dense slot-slab pool; in paged
    mode it is the ``(S, P)`` int32 map from each slot's logical page index
    to a physical page of the flat layer-0 page pool (null page 0 for
    unmapped entries). The host rebuilds and uploads it at every drain
    boundary from the scheduler's page mappings.
    """

    state: Dict[str, Any]       # model caches (+aux), slot- or page-major
    tok: jax.Array              # (S,) int32 — last emitted token per slot
    cache_len: jax.Array        # (S,) int32 — filled KV prefix per slot
    done: jax.Array             # (S,) bool — drained/empty slot mask
    n_gen: jax.Array            # (S,) int32 — tokens emitted per occupant
    budget: jax.Array           # (S,) int32 — occupant's max_new_tokens
    block_tables: Optional[jax.Array] = None    # (S, P) int32, paged only


class PoolManager:
    """Owns PoolState construction, tier copies, and slot ownership.

    Exactly ONE PoolManager backs an engine, shared by its prefill and
    decode roles — the pool is a single address space (the paper's shared
    L1), the roles are just who computes against it. ``build`` is the
    engine core's builder (:meth:`EngineCore._build`): it runs a pool
    constructor so the arrays are born on the same mesh shardings as every
    jitted fn's output.
    """

    def __init__(self, model: Any, ecfg: Any,
                 build: Callable[[Callable[[], Any]], Any]):
        self.model = model
        self.ecfg = ecfg
        self._build = build
        self._tier_copy: Dict[Tuple[str, str], Any] = {}   # jitted tier copies
        self._geom = None           # PageGeometry after init_paged_pool
        # ---- disaggregated slot ownership (role name per occupied slot).
        # Empty in combined mode: a single engine owns everything and the
        # bookkeeping would only add per-boundary host work.
        self.owner: Dict[int, str] = {}
        self.handovers = 0
        self.handover_pages = 0

    # ------------------------------------------------------- construction
    def init_pool(self, n_slots: int) -> PoolState:
        """Empty slot pool: all slots done (free), caches zeroed."""
        cfg = self.model.cfg
        if cfg.family == "encdec":
            raise NotImplementedError(
                "pooled serving targets decoder-only families; encdec "
                "requests go through one-shot generate()")
        if cfg.frontend_len:
            raise NotImplementedError(
                "pooled serving takes token prompts; frontend-embed "
                "requests go through one-shot generate()")
        from repro.models import transformer

        def make():
            state = {"caches": transformer.init_caches(cfg, n_slots,
                                                       self.ecfg.max_len)}
            zeros = lambda: jnp.zeros((n_slots,), jnp.int32)
            return PoolState(
                state=state,
                tok=jnp.full((n_slots,), self.ecfg.pad_token, jnp.int32),
                cache_len=zeros(),
                done=jnp.ones((n_slots,), bool),
                n_gen=zeros(), budget=zeros())

        return self._build(make)

    def init_paged_pool(self, sch: sched_mod.Scheduler
                        ) -> Tuple[PoolState, Dict[str, Any]]:
        """Empty paged pool + the layer-1 spill tier's device arrays.

        Layer 0 is a flat page pool shared by all slots (block tables map
        slots to pages); layer 1 mirrors it at the spill budget, plus one
        resident "seat" per spill page for recurrent SSM state (a spilled
        sequence holds at least one page, so seats cannot run out first).
        """
        geom = sch.pages
        assert geom is not None, "init_paged_pool needs a paged scheduler"
        cfg = self.model.cfg
        if cfg.family == "encdec" or cfg.frontend_len:
            raise NotImplementedError(
                "paged serving targets decoder-only token-prompt models; "
                "others go through one-shot generate()")
        from repro.models import transformer
        l0 = CODECS[getattr(geom, "layer0_codec", "fp16")]
        l1 = CODECS[getattr(geom, "layer1_codec", "fp16")]
        if (l0.name != "fp16" or l1.name != "fp16") \
                and _has_recurrent_state(cfg):
            raise ValueError(
                "quantized KV pages require attention-only models: "
                "recurrent SSM state integrates every step and has no "
                "bounded per-page error story (docs/SERVING.md)")
        self._geom = geom
        n_slots = sch.n_slots

        def make():
            state = {"caches": transformer.init_paged_caches(
                cfg, n_slots, geom.n_pages, geom.page_tokens,
                dtype=l0.dtype, quant_scales=l0.scaled)}
            spill = transformer.init_paged_caches(
                cfg, geom.n_spill_pages, geom.n_spill_pages,
                geom.page_tokens, dtype=l1.dtype, quant_scales=l1.scaled)
            zeros = lambda: jnp.zeros((n_slots,), jnp.int32)
            pool = PoolState(
                state=state,
                tok=jnp.full((n_slots,), self.ecfg.pad_token, jnp.int32),
                cache_len=zeros(), done=jnp.ones((n_slots,), bool),
                n_gen=zeros(), budget=zeros(),
                block_tables=jnp.zeros((n_slots, geom.max_pages_per_slot),
                                       jnp.int32))
            return pool, spill

        return self._build(make)

    # -------------------------------------------------------- tier copies
    def tier_copy_fn(self, src_codec: str = "fp16", dst_codec: str = "fp16"):
        """ONE jitted layer-0 <-> layer-1 copy per codec pair, shared by
        spill and restore (jit's shape-keyed cache traces each direction
        independently).

        Page pools move whole pages (gather by source ids, scatter at
        destination ids — padded entries route through the null pages);
        recurrent per-slot state moves one row between the slot axis and
        the spill seat axis. Everything stays on device.

        Same-codec tiers copy VERBATIM — int8 codes and their page scales
        move untouched, so a quantized spill -> restore round-trip is
        bit-exact (no double quantization). Cross-codec tiers (the fp8
        policy's fp8 layer 0 <-> int8 layer 1) dequantize each moved page
        to f32 and re-encode at the destination codec, writing fresh
        per-page scales when the destination is scaled.

        The destination tier is donated: the copy writes the moved pages
        in place instead of copying the whole tier.
        """
        key = (src_codec, dst_codec)
        if key in self._tier_copy:
            return self._tier_copy[key]
        from repro.models import transformer
        from repro.kernels import paged_attention as pq
        cfg = self.model.cfg
        src_c, dst_c = CODECS[src_codec], CODECS[dst_codec]
        same = src_codec == dst_codec

        def convert_pages(src_leaves, dst_leaves, pages_src, pages_dst):
            out = dict(dst_leaves)
            for name in [n for n in src_leaves if not n.endswith("_scale")]:
                sel = src_leaves[name][:, pages_src]    # (r, Psel, *page)
                if src_c.scaled:
                    scl = src_leaves[name + "_scale"][:, pages_src]
                    sel = (sel.astype(jnp.float32)
                           * scl.reshape(scl.shape + (1,) * (sel.ndim - 2)))
                else:
                    sel = sel.astype(jnp.float32)
                dst = dst_leaves[name]
                if dst_c.scaled:
                    codes, scales = pq.quantize_page_int8(
                        sel, tuple(range(2, sel.ndim)))
                    out[name] = dst.at[:, pages_dst].set(codes)
                    out[name + "_scale"] = dst_leaves[
                        name + "_scale"].at[:, pages_dst].set(scales)
                else:
                    out[name] = dst.at[:, pages_dst].set(
                        sel.astype(dst.dtype))
            return out

        def copy(src_caches, dst_caches, row_src, row_dst, pages_src,
                 pages_dst):
            def page_copy(s, d):
                return d.at[:, pages_dst].set(s[:, pages_src].astype(d.dtype))

            def row_copy(s, d):
                row = jax.lax.dynamic_slice_in_dim(s, row_src, 1, axis=1)
                return jax.lax.dynamic_update_slice_in_dim(
                    d, row.astype(d.dtype), row_dst, axis=1)

            out: Dict[str, Any] = {}
            for gname, gkey, is_paged in transformer.paged_cache_kinds(cfg):
                src_g, dst_g = src_caches[gname][gkey], dst_caches[gname][gkey]
                if not is_paged:
                    leaf = jax.tree.map(row_copy, src_g, dst_g)
                elif same:
                    leaf = jax.tree.map(page_copy, src_g, dst_g)
                else:
                    leaf = convert_pages(src_g, dst_g, pages_src, pages_dst)
                out.setdefault(gname, {})[gkey] = leaf
            return out

        self._tier_copy[key] = jax.jit(copy, donate_argnums=1)
        return self._tier_copy[key]

    def _tier_codecs(self) -> Tuple[str, str]:
        geom = self._geom
        if geom is None:
            return ("fp16", "fp16")
        return (getattr(geom, "layer0_codec", "fp16"),
                getattr(geom, "layer1_codec", "fp16"))

    @staticmethod
    def pad_pages(pages, p_max: int) -> jax.Array:
        row = np.zeros((p_max,), np.int32)
        row[:len(pages)] = pages
        return jnp.asarray(row)

    def exec_spill(self, pool: PoolState, spill: Dict[str, Any],
                   act: sched_mod.SpillAction, p_max: int) -> Dict[str, Any]:
        self.owner.pop(act.slot, None)      # preempted: the slot frees
        l0, l1 = self._tier_codecs()
        return self.tier_copy_fn(l0, l1)(
            pool.state["caches"], spill,
            jnp.asarray(act.slot, jnp.int32),
            jnp.asarray(act.seat, jnp.int32),
            self.pad_pages(act.src_pages, p_max),
            self.pad_pages(act.dst_pages, p_max))

    def exec_restore(self, pool: PoolState, spill: Dict[str, Any],
                     act: sched_mod.RestoreAction, p_max: int) -> PoolState:
        """Copy a preempted sequence back into layer 0 and re-arm its slot.

        The per-slot vectors are rebuilt from the host mirror: the KV
        frontier is one behind the emitted count (the last token's K/V is
        written by its own upcoming decode step), so decode resumes
        bit-exactly where preemption cut it."""
        req = act.req
        l0, l1 = self._tier_codecs()
        caches = self.tier_copy_fn(l1, l0)(
            spill, pool.state["caches"],
            jnp.asarray(act.seat, jnp.int32),
            jnp.asarray(act.slot, jnp.int32),
            self.pad_pages(act.src_pages, p_max),
            self.pad_pages(req.pages[:len(act.src_pages)], p_max))
        slot = act.slot
        if req.status == sched_mod.PREFILLING:
            # restored mid-chunked-prefill: no output token exists yet, so
            # only the KV frontier is re-armed; done is FORCED True (the
            # slot may have been freed by a mid-decode preemption, leaving
            # done=False on device) so the slot stays masked until its
            # final chunk lands, and the cursor resumes at the NEXT
            # boundary's prefill phase (plan order contract)
            return dataclasses.replace(
                pool, state={**pool.state, "caches": caches},
                cache_len=pool.cache_len.at[slot].set(req.cache_len),
                done=pool.done.at[slot].set(True))
        return dataclasses.replace(
            pool, state={**pool.state, "caches": caches},
            tok=pool.tok.at[slot].set(int(req.tokens[-1])),
            cache_len=pool.cache_len.at[slot].set(req.cache_len),
            done=pool.done.at[slot].set(False),
            n_gen=pool.n_gen.at[slot].set(len(req.tokens)),
            budget=pool.budget.at[slot].set(req.max_new_tokens))

    # ---------------------------------------------------------- ownership
    def claim(self, slot: int, role: str) -> None:
        """Record which role a slot's work is issued by (disaggregated
        serving only; combined engines never populate the map)."""
        self.owner[slot] = role

    def release(self, slot: int) -> None:
        self.owner.pop(slot, None)

    def transfer_ownership(self, slot: int, pages: List[int], *,
                           src: str = PREFILL_ROLE,
                           dst: str = DECODE_ROLE) -> None:
        """Hand a slot (and its mapped pages) from ``src`` to ``dst``.

        This is pure bookkeeping — the zero-copy invariant of the shared
        pool: the slot's pages already live in the layer-0 arrays both
        roles compute against, so handover flips one table entry and the
        decode role's next block-table upload carries the row. Raises if
        ``src`` does not own the slot (a handover for a slot the prefill
        role lost to preemption would silently corrupt routing).
        """
        cur = self.owner.get(slot)
        if cur != src:
            raise RuntimeError(
                f"handover of slot {slot}: owned by {cur!r}, expected "
                f"{src!r} — page handover must follow the final prefill "
                f"chunk of the owning role")
        self.owner[slot] = dst
        self.handovers += 1
        self.handover_pages += len(pages)
