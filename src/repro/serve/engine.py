"""Serving engine over the pooled KV cache: slot-based continuous batching.

Two serving surfaces share one decode substrate:

  * :meth:`Engine.generate` — one-shot batched greedy decode (every row
    shares a prompt length). The decode loop runs as jitted
    ``lax.scan`` chunks of ``sync_interval`` steps; done rows are masked
    ON-DEVICE with ``jnp.where`` and the host reads the done mask only at
    chunk boundaries (one explicit ``device_get`` per chunk, counted in
    ``last_stats["host_syncs"]``) — there is NO per-token device->host
    round-trip.
  * :meth:`Engine.serve` — continuous batching. The KV cache is a pool of
    ``n_slots`` sequence slots (:meth:`init_pool`); a
    :class:`~repro.serve.scheduler.Scheduler` admits queued requests into
    free slots at drain boundaries, a jitted admission step prefills the
    prompt and scatters its cache rows into the pool
    (:meth:`~repro.models.api.Model.slot_update`) without touching in-flight
    rows, and every chunk decodes ALL slots in one batched step with
    per-slot ``cache_len`` vectors. Finished sequences free their slots for
    immediate reuse. When the scheduler carries a
    :class:`~repro.serve.scheduler.PageGeometry`, serving switches to the
    **paged two-tier pool** (:meth:`init_paged_pool`): KV storage is a flat
    layer-0 page pool addressed through per-slot block tables, admission
    reserves *pages* instead of ``max_len`` slabs, and when layer 0 runs
    out the youngest resident spills verbatim to the layer-1 tier — the
    paper's two-die capacity split, applied to serving. A scheduler built
    with ``prefix_share=True`` additionally executes prefix-index hits as
    **suffix-only prefills** over ref-counted shared pages
    (:meth:`PrefillRole.shared_paged_admit`), turning shared-prefix TTFT
    compute from O(prompt) into O(suffix) — DESIGN.md §Prefix sharing &
    copy-on-write.

The engine itself is a composition of *roles* over a shared pool
(DESIGN.md §Disaggregated serving):

  * :class:`EngineCore` — jit-fn caches, mesh scope, device placement,
    host IO (the single ``_fetch`` read path), timing, and the one
    bucketing rule every jit-cache key goes through.
  * :class:`PrefillRole` — admissions, suffix-only prefills, and chunked
    prefill steps (compute-heavy, prompt-shaped work).
  * :class:`DecodeRole` — the batched decode / speculative-verify chunks
    (pool-sweep, latency-shaped work).
  * :class:`~repro.serve.pool.PoolManager` — pool construction, the
    layer-0 <-> layer-1 tier copies, and slot ownership (the
    ``transfer_ownership`` page-handover primitive).

A combined :class:`Engine` runs both roles in one loop — every test and
benchmark goes through the role split. ``EngineConfig(disaggregate=True)``
routes the SAME loop by role: admissions and prompt chunks are issued (and
their host syncs attributed) by the prefill role, decode by the decode
role, and at a request's final prefill chunk the scheduler emits a
``HandoverStep`` the engine executes as a zero-copy ownership flip —
the slot's block-table row starts appearing in the decode role's uploaded
table; no KV bytes move. This is the serving analogue of the paper's
compute-die / memory-die split: one shared pool address space, physically
distinct engines for the two phases of the workload.

The cache layout is the pooled-memory design (DESIGN.md §Pooled KV cache):
sequence dim sharded across the `model` axis, so aggregate pod HBM is one
big KV pool — MemPool's shared L1, at cluster scale. The slot count is
derived from the SAME CapacityPartition budget formula as kernel tiles
(:func:`repro.serve.scheduler.derive_n_slots`).

Kernel block plans are obtained ONCE at engine construction from the model's
planner (sized for ``max_len`` on the current hardware target) and threaded
into every prefill/decode call — serving never re-plans per step.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed import sharding as shd
from repro.kernels.paged_attention import walk_pages_per_block
from repro.models import transformer
from repro.models.api import Model
from repro.serve import scheduler as sched_mod
from repro.serve import spans
from repro.serve import speculate as spec_mod
from repro.serve.pool import (DECODE_ROLE, PREFILL_ROLE, PoolManager,
                              PoolState)

__all__ = ["Engine", "EngineConfig", "EngineCore", "PrefillRole",
           "DecodeRole", "PoolState", "ServeReport", "serving_params",
           "init_serving_params", "walk_counts"]


#: Parameter leaves (final path component) that every use casts to the bf16
#: compute dtype first: ``layers.linear`` weights, the embedding tables
#: (``layers.embed`` / ``unembed_logits``) and the MLA absorbed ``wkv_b``.
#: Biases, norm scales, SSM decay/skip terms, routers and the dense-path
#: expert weights are used in f32 and stay f32.
SERVING_BF16_LEAVES = frozenset({
    "embed", "unembed", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
    "wq_a", "wq_b", "wkv_a", "wkv_b", "in_proj", "x_proj", "dt_proj",
    "out_proj"})


def serving_params(params: Any) -> Any:
    """Cast :data:`SERVING_BF16_LEAVES` to bf16, once.

    Served tokens stay bit-identical (each use would have made the same
    cast), the weights take half the bytes, and XLA no longer hoists the
    per-use converts of the stacked layer weights out of the decode scan —
    which held a second, bf16 copy of every weight beside the f32 one."""
    def cast(path, leaf):
        if (getattr(path[-1], "key", None) in SERVING_BF16_LEAVES
                and leaf.dtype == jnp.float32):
            return leaf.astype(jnp.bfloat16)
        return leaf
    return jax.tree_util.tree_map_with_path(cast, params)


def init_serving_params(model: Model, key, mesh=None) -> Any:
    """Random weights from ``key``, made on the device already cast by
    :func:`serving_params` and, with ``mesh``, already tensor-parallel
    placed — the f32 copy of a full-width model never exists whole."""
    init = lambda k: serving_params(model.init(k))
    out = None
    if mesh is not None:
        out = shd.named_shardings(jax.eval_shape(init, key), mesh)
    return jax.jit(init, out_shardings=out)(key)


def walk_counts(frontiers: Iterable[int], n: int, n_slots: int,
                geom: sched_mod.PageGeometry) -> Dict[str, int]:
    """Page-walk grid cells of one paged decode chunk of ``n`` steps, as
    ``engine.generate`` args: ``walk_blocks``, the cells (slot, block of
    :func:`walk_pages_per_block` pages) that hold a visible page at the
    chunk's last step, summed over the decoding slots whose frontiers
    were ``frontiers`` at its start; ``walk_grid``, the grid's cells, every
    slot times the blocks of a block-table row. Their ratio is the share of
    the grid that fetches anything (for layers without a window)."""
    p_max = geom.max_pages_per_slot
    ppb = walk_pages_per_block(geom.page_tokens, p_max)
    n_blocks = -(-p_max // ppb)
    bk = ppb * geom.page_tokens
    blocks = sum(min((f + n - 1) // bk, n_blocks - 1) + 1 for f in frontiers)
    return {"walk_blocks": blocks, "walk_grid": n_slots * n_blocks}


@dataclasses.dataclass
class EngineConfig:
    """max_len bounds prompt + generation (the KV slot depth).

    ``sync_interval`` is the decode-chunk length: how many on-device steps
    run between host syncs (batch-drain boundaries). ``prompt_pad_multiple``
    right-pads slot prompts up to a multiple to bound prefill recompiles;
    it must stay ``None`` (exact-length prefill) for models with recurrent
    SSM layers, whose state would integrate the pad tokens.

    ``speculate_tokens`` (k) turns on self-drafting speculative decoding in
    the serve loops (DESIGN.md §Speculative decoding): each drain boundary
    proposes up to k draft tokens per live slot from the slot's own
    emitted+prompt history and scores them all in ONE width-(k+1) verify
    forward, emitting accepted-prefix + 1 tokens per slot per boundary.
    Greedy outputs are bit-exact with ``speculate_tokens=0``. Requires
    attention-only models (recurrent SSM state cannot roll back rejected
    draft tokens); size k with
    :func:`repro.serve.scheduler.derive_speculate_tokens`.

    ``phase_timing`` turns on the per-phase wall-clock breakdown
    (prefill / insert / generate / drain / handover) in ``last_stats`` —
    benchmark mode only: each phase blocks on its device work, which
    serializes the dispatch pipeline the serve loop otherwise overlaps.

    ``mesh`` (a ``jax.sharding.Mesh``, e.g. from
    :func:`repro.launch.mesh.make_host_mesh`) runs every jitted engine
    function under that mesh: model weights are placed tensor-parallel
    (``repro.distributed.sharding.named_shardings``), KV pools/pages are
    placed on the head axis when the model's heads divide the `model` axis
    (DESIGN.md §Sharded serving), and GSPMD partitions the admission /
    decode / verify computations. ``None`` (default) is today's
    single-device path, bit-identical by construction; a 1x1 mesh is also
    bit-identical (every constraint resolves to replication). The
    one-host-sync-per-drain-boundary discipline is mesh-invariant: the
    block-table upload (host->device) and the drain fetch are the only
    host <-> device edges per boundary, regardless of mesh size.

    ``disaggregate`` splits serving into prefill-role and decode-role
    engines over the shared paged pool (DESIGN.md §Disaggregated serving):
    the scheduler routes PREFILLING slots to the prefill role and emits a
    page handover at each request's final prefill chunk; each role issues
    at most ONE host sync per drain boundary, and outputs stay
    bit-identical to the combined engine. Requires the paged pool
    (``Scheduler(pages=...)``).
    """

    max_len: int
    eos_token: int = 1
    greedy: bool = True
    sync_interval: int = 8
    pad_token: int = 0
    prompt_pad_multiple: Optional[int] = None
    speculate_tokens: int = 0
    phase_timing: bool = False
    mesh: Optional[Any] = None
    disaggregate: bool = False


@dataclasses.dataclass
class ServeReport:
    """Result of one :meth:`Engine.serve` run over a request stream."""

    requests: List[sched_mod.Request]
    stats: Dict[str, Any]

    @property
    def outputs(self) -> Dict[int, List[int]]:
        return {r.rid: r.tokens for r in self.requests}


class EngineCore:
    """The substrate both engine roles share: parameters (mesh-placed),
    kernel plans, jit-fn caches, device placement, host IO, and timing.

    Keeping every jit cache here — not on the roles — means a combined
    engine and a disaggregated one compile the SAME function set, and the
    equivalence matrix's bit-identity cells reuse compilations across
    modes. The core is deliberately thin: it never looks at a scheduler
    and runs no serve loop.

    Every jitted step that takes the pool donates it: the serve loops
    thread the pool through the steps functionally, so a step writes its
    output into the argument's buffers and holds one pool, not two. A pool
    passed to a step must not be used again.
    """

    def __init__(self, model: Model, params: Any, ecfg: EngineConfig):
        self.model = model
        self.mesh = ecfg.mesh
        params = serving_params(params)
        if self.mesh is not None:
            # tensor-parallel weight placement; cache pools are built placed
            # by _build and the jitted fns run under _mesh_scope
            params = jax.device_put(
                params, shd.named_shardings(params, self.mesh))
        self.params = params
        self.ecfg = ecfg
        # one capacity-partitioned plan set for the whole engine lifetime
        self.plans = model.kernel_plans(ecfg.max_len, ecfg.max_len)
        self._chunk_fns: Dict[int, Any] = {}        # one-shot decode chunks
        self._pool_chunk_fns: Dict[int, Any] = {}   # pooled decode chunks
        self._verify_fns: Dict[int, Any] = {}       # speculative verify, by k
        self._admit = None                          # dense admission
        self._paged_admit_fns: Dict[Any, Any] = {}  # keyed by page geometry
        self._suffix_admit_fns: Dict[Any, Any] = {}  # + static prefix_len
        # chunked prefill (DESIGN.md §Chunked prefill): jit variants keyed
        # by POWER-OF-TWO padded chunk length (+ emit_first), never by the
        # runtime cursor — O(log chunk_tokens) compiles total
        self._chunk_prefill_fns: Dict[Any, Any] = {}        # paged
        self._dense_chunk_prefill_fns: Dict[Any, Any] = {}  # dense
        self.last_stats: Dict[str, Any] = {}
        self.last_phase: Optional[spans.Span] = None   # set by _timed

    def _has_ssm(self) -> bool:
        return any(kind.attn == "mamba"
                   for group in self.model.cfg.layer_groups()
                   for kind in group.pattern)

    # -------------------------------------------------------------- mesh
    def _mesh_scope(self):
        """Ambient-mesh context for every traced/jitted engine call.

        With ``EngineConfig(mesh=...)`` set, entering the scope makes the
        ``repro.distributed.sharding.shard`` constraints inside the model
        live (head-axis KV placement, batch sharding); without one it is a
        null context and every constraint no-ops — the single-device path
        is untouched."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.mesh)

    def _build(self, make):
        """Run ``make`` (no arguments, returns a cache/pool tree) so its
        outputs are born on their mesh shardings: head-axis placement for
        GQA caches/pages, replication for latent/SSM state and scalars
        (``spec_for_cache``). Under a mesh it is jitted with those
        ``out_shardings``, so no device ever holds the whole pool — built
        whole and then placed, a four-chip pool does not fit one chip.
        Without a mesh it is called directly."""
        if self.mesh is None:
            return make()
        out = shd.named_shardings(jax.eval_shape(make), self.mesh)
        return jax.jit(make, out_shardings=out)()

    # ------------------------------------------------------------ host IO
    def _fetch(self, tree, role: Optional[str] = None):
        """The ONLY device->host read path. One explicit transfer per call,
        issued at batch-drain boundaries; counted for the regression test.
        ``role`` attributes the sync when serving disaggregated — the
        per-role sync discipline is each role issues at most one fetch per
        boundary."""
        self.last_stats["host_syncs"] = self.last_stats.get("host_syncs", 0) + 1
        if role is not None:
            by = self.last_stats.setdefault("host_syncs_by_role", {})
            by[role] = by.get(role, 0) + 1
        return jax.device_get(tree)

    def _timed(self, phase: str, fn, *args, role: Optional[str] = None,
               **span_args):
        """Run ``fn`` inside the span ``engine.<phase>`` (``span_args``, and
        ``role`` when given, are its args; :mod:`repro.serve.spans`) and
        keep that span as ``last_phase``. In ``phase_timing`` mode the span
        closes only after the device work is done, and its wall time is
        charged to ``last_stats['phase_s'][phase]`` — and, when ``role`` is
        given, to ``last_stats['role_s'][role]`` (the disaggregated
        per-role busy breakdown). Off by default: blocking per phase would
        serialize the dispatch pipeline the serve loop overlaps."""
        if role is not None:
            span_args["role"] = role
        with spans.span("engine." + phase, **span_args) as sp:
            out = fn(*args)
            if self.ecfg.phase_timing:
                jax.block_until_ready(out)
        self.last_phase = sp
        if self.ecfg.phase_timing:
            acc = self.last_stats.setdefault("phase_s", {})
            acc[phase] = acc.get(phase, 0.0) + sp.seconds
            if role is not None:
                racc = self.last_stats.setdefault("role_s", {})
                racc[role] = racc.get(role, 0.0) + sp.seconds
        return out

    # --------------------------------------------------------- bucketing
    @staticmethod
    def bucket_len(n: int, limit: int, *, start: int = 0,
                   multiple: Optional[int] = None) -> int:
        """THE bucketing rule for every shape-keyed jit cache.

        Default mode: next power of two >= ``n``, clamped to ``limit`` —
        the static lengths chunk prefill compiles for. When the padded
        chunk would overrun the cache depth from ``start`` (the slot-depth
        edge), the exact length is used instead: a traced-start cache
        write would clamp backwards over earlier chunks (rare tail
        variant; never hit while prompt + chunk fit the depth).

        ``multiple`` mode: round up to a multiple instead (the
        ``prompt_pad_multiple`` admission bucketing), clamped to ``limit``
        so the padded buffer still fits the slot's KV depth.

        One helper, three former call sites (`_bucket_len`, `_pad_prompt`,
        the chunk-prefill edge) — the compile-cache key sequence is pinned
        by ``tests/test_chunked_prefill.py``.
        """
        n = int(n)
        if multiple:
            return min(-(-n // multiple) * multiple, limit)
        padded = min(1 << (n - 1).bit_length(), limit)
        if start + padded > limit:
            return n
        return padded


class PrefillRole:
    """The prefill-role engine: admissions (whole-prompt, suffix-only, and
    chunked) over the shared pool. Prompt-shaped, compute-heavy work — the
    "logic die" half of the role split. Owns no device state: pools come
    in and go out of every call; jitted fns live in the shared core."""

    name = PREFILL_ROLE

    def __init__(self, core: EngineCore, pools: PoolManager):
        self.core = core
        self.pools = pools

    def _pad_prompt(self, prompt: np.ndarray) -> Tuple[np.ndarray, int]:
        core = self.core
        true_len = int(prompt.shape[0])
        if true_len > core.ecfg.max_len:
            raise ValueError(
                f"prompt of {true_len} tokens exceeds the KV slot depth "
                f"(max_len={core.ecfg.max_len})")
        m = core.ecfg.prompt_pad_multiple
        if not m:
            return prompt, true_len
        padded = core.bucket_len(true_len, core.ecfg.max_len, multiple=m)
        if padded == true_len:
            return prompt, true_len
        out = np.full((padded,), core.ecfg.pad_token, np.int32)
        out[:true_len] = prompt
        return out, true_len

    def _make_admit_fn(self):
        """Jitted admission: prefill one prompt row and scatter it into the
        pool at ``slot`` — in-flight slots are untouched (pure row insert).
        One function; jit's shape-keyed cache retraces per padded prompt
        length (bounded by ``prompt_pad_multiple`` bucketing)."""
        core = self.core
        cfg, ecfg, plans = core.model.cfg, core.ecfg, core.plans

        def run(params, tokens, true_len, budget, slot, pool: PoolState):
            last = (true_len - 1)[None]                     # (1,) gather
            logits, row = core.model.prefill(
                params, {"tokens": tokens}, ecfg.max_len, plans=plans,
                last_pos=last)
            first = jnp.argmax(logits[0, -1, :cfg.vocab_size])
            first = first.astype(jnp.int32)
            state = core.model.slot_update(pool.state, row, slot)
            kv_len = true_len                               # filled prefix
            done0 = ((first == ecfg.eos_token) | (budget <= 1)
                     | (kv_len >= ecfg.max_len))
            return PoolState(
                state=state,
                tok=pool.tok.at[slot].set(first),
                cache_len=pool.cache_len.at[slot].set(kv_len),
                done=pool.done.at[slot].set(done0),
                n_gen=pool.n_gen.at[slot].set(1),
                budget=pool.budget.at[slot].set(budget)), first

        return jax.jit(run, donate_argnums=5)

    def admit_into_slot(self, pool: PoolState, slot: int,
                        prompt: np.ndarray, max_new_tokens: int
                        ) -> Tuple[PoolState, jax.Array]:
        """Prefill ``prompt`` into ``slot``. Returns (pool, first_token) —
        the token stays on device; callers fetch it at the next drain."""
        core = self.core
        if core._admit is None:
            core._admit = self._make_admit_fn()
        tokens, true_len = self._pad_prompt(np.asarray(prompt, np.int32))
        return core._admit(core.params, tokens[None],
                           jnp.asarray(true_len, jnp.int32),
                           jnp.asarray(max_new_tokens, jnp.int32),
                           jnp.asarray(slot, jnp.int32), pool)

    # ------------------------------------------------------ paged admission
    def _make_paged_admit_fn(self, geom: sched_mod.PageGeometry):
        """Jitted paged admission: prefill one prompt row at the pool's
        page-aligned depth, cut it into pages and scatter them at the
        slot's block-table row. In-flight pages are untouched."""
        core = self.core
        cfg, ecfg, plans = core.model.cfg, core.ecfg, core.plans
        depth, pt = geom.depth, geom.page_tokens

        def run(params, tokens, true_len, budget, slot, block_row,
                pool: PoolState):
            last = (true_len - 1)[None]                 # (1,) gather
            logits, row = core.model.prefill(
                params, {"tokens": tokens}, depth, plans=plans, last_pos=last)
            first = jnp.argmax(logits[0, -1, :cfg.vocab_size])
            first = first.astype(jnp.int32)
            state = core.model.slot_update_paged(pool.state, row, slot,
                                                 block_row, pt)
            kv_len = true_len
            done0 = ((first == ecfg.eos_token) | (budget <= 1)
                     | (kv_len >= ecfg.max_len))
            return dataclasses.replace(
                pool, state=state,
                tok=pool.tok.at[slot].set(first),
                cache_len=pool.cache_len.at[slot].set(kv_len),
                done=pool.done.at[slot].set(done0),
                n_gen=pool.n_gen.at[slot].set(1),
                budget=pool.budget.at[slot].set(budget)), first

        return jax.jit(run, donate_argnums=6)

    def paged_admit(self, pool: PoolState, slot: int,
                    req: sched_mod.Request, geom: sched_mod.PageGeometry
                    ) -> Tuple[PoolState, jax.Array]:
        core = self.core
        tokens, true_len = self._pad_prompt(np.asarray(req.prompt, np.int32))
        block_row = self.pools.pad_pages(req.pages, geom.max_pages_per_slot)
        key = (geom.depth, geom.page_tokens)
        if key not in core._paged_admit_fns:
            core._paged_admit_fns[key] = self._make_paged_admit_fn(geom)
        return core._paged_admit_fns[key](
            core.params, tokens[None], jnp.asarray(true_len, jnp.int32),
            jnp.asarray(req.max_new_tokens, jnp.int32),
            jnp.asarray(slot, jnp.int32), block_row, pool)

    def _make_suffix_admit_fn(self, geom: sched_mod.PageGeometry,
                              prefix_len: int):
        """Jitted cache-hit admission: prefill ONLY the unmatched suffix.

        The shared prefix pages (plus the copy-on-write source, when the
        match ends mid-page) are gathered into a dense batch-1 view, the
        suffix runs through ``Model.prefill`` at a static ``prefix_len``
        offset (RoPE positions and causal masks continue where the shared
        prefix ends — bit-identical to the same rows of a full prefill),
        and the result is scattered back through ``write_row``, whose
        entries for shared pages point at null page 0: shared history is
        never written, and the frontier page lands in the request's fresh
        private page (the COW copy rides the gather->scatter cycle).
        TTFT compute drops from O(prompt) to O(suffix).
        """
        core = self.core
        cfg, ecfg, plans = core.model.cfg, core.ecfg, core.plans
        depth, pt = geom.depth, geom.page_tokens

        def run(params, tokens, true_len, budget, slot, read_row, write_row,
                pool: PoolState):
            prefix = core.model.gather_row_paged(pool.state, read_row, pt)
            last = (true_len - 1)[None]                 # (1,) gather
            logits, row = core.model.prefill(
                params, {"tokens": tokens}, depth, plans=plans, last_pos=last,
                prefix_len=prefix_len, prefix_state=prefix)
            first = jnp.argmax(logits[0, -1, :cfg.vocab_size])
            first = first.astype(jnp.int32)
            state = core.model.slot_update_paged(pool.state, row, slot,
                                                 write_row, pt)
            kv_len = true_len + prefix_len
            done0 = ((first == ecfg.eos_token) | (budget <= 1)
                     | (kv_len >= ecfg.max_len))
            return dataclasses.replace(
                pool, state=state,
                tok=pool.tok.at[slot].set(first),
                cache_len=pool.cache_len.at[slot].set(kv_len),
                done=pool.done.at[slot].set(done0),
                n_gen=pool.n_gen.at[slot].set(1),
                budget=pool.budget.at[slot].set(budget)), first

        return jax.jit(run, donate_argnums=7)

    def shared_paged_admit(self, pool: PoolState, slot: int,
                           req: sched_mod.Request,
                           geom: sched_mod.PageGeometry
                           ) -> Tuple[PoolState, jax.Array]:
        """Execute a prefix-index-hit admission planned by the scheduler.

        ``read_row`` maps the pages the suffix attends over: the shared
        full pages, plus — when the match ends mid-page — the COW *source*
        page at the frontier index. ``write_row`` maps where suffix K/V
        lands: null (page 0) under the shared prefix, the request's own
        fresh pages from the frontier on. The frontier page is therefore
        read from the canonical copy but written to a private one.
        """
        core = self.core
        pt, p_max = geom.page_tokens, geom.max_pages_per_slot
        suffix = np.asarray(req.prompt, np.int32)[req.prefix_len:]
        tokens, true_len = self._pad_prompt(suffix)
        if req.prefix_len + tokens.shape[0] > geom.depth:
            tokens = tokens[:geom.depth - req.prefix_len]   # trim pad only
        f_w = req.prefix_len // pt                  # frontier logical page
        read = np.zeros((p_max,), np.int32)
        read[:req.n_shared] = req.pages[:req.n_shared]
        if req.cow_src >= 0:
            read[f_w] = req.cow_src
        write = np.zeros((p_max,), np.int32)
        write[f_w:len(req.pages)] = req.pages[f_w:]
        key = (geom.depth, pt, req.prefix_len, tokens.shape[0])
        if key not in core._suffix_admit_fns:
            core._suffix_admit_fns[key] = self._make_suffix_admit_fn(
                geom, req.prefix_len)
        return core._suffix_admit_fns[key](
            core.params, tokens[None], jnp.asarray(true_len, jnp.int32),
            jnp.asarray(req.max_new_tokens, jnp.int32),
            jnp.asarray(slot, jnp.int32), jnp.asarray(read),
            jnp.asarray(write), pool)

    # ------------------------------------------------- chunked prefill
    def _make_chunk_prefill_fn(self, geom: sched_mod.PageGeometry,
                               n_tok: int, emit_first: bool):
        """Jitted partial-prefill step: run ONE chunk of a prompt and
        scatter its K/V into the request's pages (DESIGN.md §Chunked
        prefill).

        The chunk cursor ``start`` and true length ``true_n`` are TRACED
        int32 scalars — the jit cache is keyed only by the power-of-two
        padded chunk length (plus ``emit_first``), never by where in the
        prompt the chunk lands, so a 4k-token prompt compiles the same
        O(log chunk_tokens) variants as a 64-token one. A traced cursor
        rides the same resumed-prefill path as the static-offset suffix
        admission: positions and causal masks continue at ``start``
        (bit-identical rows), and the traced offset forces the jnp
        reference attention (the Pallas kernel needs a static grid
        offset). Non-final chunks only advance ``cache_len`` — the slot
        stays done-masked, so the interleaved decode chunk freezes it for
        free. The final chunk emits the first output token and arms the
        slot exactly like an unchunked admission.
        """
        core = self.core
        cfg, ecfg, plans = core.model.cfg, core.ecfg, core.plans
        depth, pt = geom.depth, geom.page_tokens

        def run(params, tokens, start, true_n, budget, slot, read_row,
                write_row, pool: PoolState):
            prefix = core.model.gather_row_paged(pool.state, read_row, pt)
            last = (true_n - 1)[None]                   # (1,) gather
            logits, row = core.model.prefill(
                params, {"tokens": tokens}, depth, plans=plans, last_pos=last,
                prefix_len=start, prefix_state=prefix)
            state = core.model.slot_update_paged(pool.state, row, slot,
                                                 write_row, pt)
            new_len = start + true_n
            if not emit_first:
                # done=True is NOT redundant: a slot freed by preempting a
                # mid-decode request still carries done=False on device —
                # without the mask the interleaved decode chunk would
                # decode the half-prefilled slot
                return dataclasses.replace(
                    pool, state=state,
                    cache_len=pool.cache_len.at[slot].set(new_len),
                    done=pool.done.at[slot].set(True),
                ), jnp.zeros((), jnp.int32)
            first = jnp.argmax(logits[0, -1, :cfg.vocab_size])
            first = first.astype(jnp.int32)
            done0 = ((first == ecfg.eos_token) | (budget <= 1)
                     | (new_len >= ecfg.max_len))
            return dataclasses.replace(
                pool, state=state,
                tok=pool.tok.at[slot].set(first),
                cache_len=pool.cache_len.at[slot].set(new_len),
                done=pool.done.at[slot].set(done0),
                n_gen=pool.n_gen.at[slot].set(1),
                budget=pool.budget.at[slot].set(budget)), first

        return jax.jit(run, donate_argnums=8)

    def exec_prefill_chunk(self, pool: PoolState,
                           step: sched_mod.PrefillStep,
                           geom: sched_mod.PageGeometry
                           ) -> Tuple[PoolState, jax.Array]:
        """Execute one planned :class:`~repro.serve.scheduler.PrefillStep`.

        ``read_row`` maps every page holding KV the chunk attends over:
        the request's own pages below the cursor — which are the SHARED
        prefix pages for its leading entries — plus the copy-on-write
        source when the first chunk starts at a mid-page prefix match.
        ``write_row`` maps the pages the chunk's K/V lands in, from the
        cursor's page on (whole-page scatter re-writes the frontier page's
        earlier tokens with the very content just gathered, so a COW source
        is copied private on the first chunk for free)."""
        core = self.core
        req = step.req
        pt, p_max = geom.page_tokens, geom.max_pages_per_slot
        n_pad = core.bucket_len(step.n_tokens, geom.depth, start=step.start)
        tokens = np.full((n_pad,), core.ecfg.pad_token, np.int32)
        tokens[:step.n_tokens] = np.asarray(req.prompt, np.int32)[
            step.start:step.start + step.n_tokens]
        f_r = -(-step.start // pt)              # pages covering [0, start)
        read = np.zeros((p_max,), np.int32)
        read[:f_r] = req.pages[:f_r]
        if step.start == req.prefix_len and req.cow_src >= 0:
            read[step.start // pt] = req.cow_src
        f_w = step.start // pt                  # cursor's (frontier) page
        end_pages = geom.pages_for(step.start + step.n_tokens)
        write = np.zeros((p_max,), np.int32)
        write[f_w:end_pages] = req.pages[f_w:end_pages]
        key = (geom.depth, pt, n_pad, step.final)
        if key not in core._chunk_prefill_fns:
            core._chunk_prefill_fns[key] = self._make_chunk_prefill_fn(
                geom, n_pad, step.final)
        return core._chunk_prefill_fns[key](
            core.params, tokens[None], jnp.asarray(step.start, jnp.int32),
            jnp.asarray(step.n_tokens, jnp.int32),
            jnp.asarray(req.max_new_tokens, jnp.int32),
            jnp.asarray(step.slot, jnp.int32), jnp.asarray(read),
            jnp.asarray(write), pool)

    def _make_dense_chunk_prefill_fn(self, n_tok: int, emit_first: bool):
        """Dense-pool analog of :meth:`_make_chunk_prefill_fn`: the chunk
        attends over the slot's own slab (earlier chunks' K/V gathered by
        :meth:`~repro.models.api.Model.gather_row`) and the whole updated
        row is scattered back. Same traced cursor, same bucketed jit key."""
        core = self.core
        cfg, ecfg, plans = core.model.cfg, core.ecfg, core.plans

        def run(params, tokens, start, true_n, budget, slot,
                pool: PoolState):
            prefix = core.model.gather_row(pool.state, slot)
            last = (true_n - 1)[None]                   # (1,) gather
            logits, row = core.model.prefill(
                params, {"tokens": tokens}, ecfg.max_len, plans=plans,
                last_pos=last, prefix_len=start, prefix_state=prefix)
            state = core.model.slot_update(pool.state, row, slot)
            new_len = start + true_n
            if not emit_first:
                return dataclasses.replace(
                    pool, state=state,
                    cache_len=pool.cache_len.at[slot].set(new_len),
                    done=pool.done.at[slot].set(True),
                ), jnp.zeros((), jnp.int32)
            first = jnp.argmax(logits[0, -1, :cfg.vocab_size])
            first = first.astype(jnp.int32)
            done0 = ((first == ecfg.eos_token) | (budget <= 1)
                     | (new_len >= ecfg.max_len))
            return dataclasses.replace(
                pool, state=state,
                tok=pool.tok.at[slot].set(first),
                cache_len=pool.cache_len.at[slot].set(new_len),
                done=pool.done.at[slot].set(done0),
                n_gen=pool.n_gen.at[slot].set(1),
                budget=pool.budget.at[slot].set(budget)), first

        return jax.jit(run, donate_argnums=6)

    def exec_dense_chunk(self, pool: PoolState, step: sched_mod.PrefillStep
                         ) -> Tuple[PoolState, jax.Array]:
        core = self.core
        req = step.req
        n_pad = core.bucket_len(step.n_tokens, core.ecfg.max_len,
                                start=step.start)
        tokens = np.full((n_pad,), core.ecfg.pad_token, np.int32)
        tokens[:step.n_tokens] = np.asarray(req.prompt, np.int32)[
            step.start:step.start + step.n_tokens]
        key = (n_pad, step.final)
        if key not in core._dense_chunk_prefill_fns:
            core._dense_chunk_prefill_fns[key] = \
                self._make_dense_chunk_prefill_fn(n_pad, step.final)
        return core._dense_chunk_prefill_fns[key](
            core.params, tokens[None], jnp.asarray(step.start, jnp.int32),
            jnp.asarray(step.n_tokens, jnp.int32),
            jnp.asarray(req.max_new_tokens, jnp.int32),
            jnp.asarray(step.slot, jnp.int32), pool)


class DecodeRole:
    """The decode-role engine: batched decode and speculative-verify
    chunks over the shared pool. Pool-sweep, latency-shaped work — the
    "memory die" half of the role split. In disaggregated mode its
    uploaded block table carries rows ONLY for slots it owns (handover
    makes a row appear); done-masked slots it does not own write their
    junk K/V to the null page instead of their own pages — positions at
    or past a prefill cursor are never read, so outputs are unchanged."""

    name = DECODE_ROLE

    def __init__(self, core: EngineCore, pools: PoolManager):
        self.core = core
        self.pools = pools

    def decode_chunk(self, n: int):
        """Jitted: n decode steps with on-device EOS masking (lax.scan) —
        the one-shot :meth:`Engine.generate` substrate."""
        core = self.core
        if n not in core._chunk_fns:
            cfg, ecfg, plans = core.model.cfg, core.ecfg, core.plans

            def run(params, tok, state, cache_len, done):
                def step(carry, _):
                    tok, state, cache_len, done = carry
                    logits, state = core.model.decode_step(
                        params, tok[:, None], state, cache_len, plans=plans)
                    nxt = jnp.argmax(logits[:, -1, :cfg.vocab_size], axis=-1)
                    tok = jnp.where(done, ecfg.eos_token, nxt)
                    done = done | (tok == ecfg.eos_token)
                    return (tok, state, cache_len + 1, done), tok

                carry, toks = jax.lax.scan(step, (tok, state, cache_len, done),
                                           None, length=n)
                tok, state, cache_len, done = carry
                return jnp.moveaxis(toks, 0, 1), tok, state, cache_len, done

            core._chunk_fns[n] = jax.jit(run)
        return core._chunk_fns[n]

    def pool_chunk(self, n: int):
        """Jitted: n batched decode steps over ALL slots with per-slot
        cache_len vectors and on-device done masking. Emits per-step
        (token, was_active) pairs; the host sees them only after the chunk."""
        core = self.core
        if n not in core._pool_chunk_fns:
            cfg, ecfg, plans = core.model.cfg, core.ecfg, core.plans

            def run(params, pool: PoolState):
                def step(pool: PoolState, _):
                    logits, state = core.model.decode_step(
                        params, pool.tok[:, None], pool.state, pool.cache_len,
                        plans=plans, block_tables=pool.block_tables)
                    nxt = jnp.argmax(logits[:, -1, :cfg.vocab_size], axis=-1)
                    was_done = pool.done
                    tok = jnp.where(was_done, ecfg.eos_token,
                                    nxt).astype(jnp.int32)
                    n_gen = jnp.where(was_done, pool.n_gen, pool.n_gen + 1)
                    cache_len = jnp.where(was_done, pool.cache_len,
                                          pool.cache_len + 1)
                    done = (was_done | (tok == ecfg.eos_token)
                            | (n_gen >= pool.budget)
                            | (cache_len >= ecfg.max_len))
                    new = PoolState(state=state, tok=tok, cache_len=cache_len,
                                    done=done, n_gen=n_gen,
                                    budget=pool.budget,
                                    block_tables=pool.block_tables)
                    return new, (tok, ~was_done)

                pool, (toks, valid) = jax.lax.scan(step, pool, None, length=n)
                return pool, toks, valid        # (n, S) each

            core._pool_chunk_fns[n] = jax.jit(run, donate_argnums=1)
        return core._pool_chunk_fns[n]

    # ------------------------------------------- speculative verify chunk
    def verify_fn(self, k: int):
        """Jitted speculative boundary: ONE width-(k+1) verify forward over
        ALL slots, folded into the pool's done-masked updates (DESIGN.md
        §Speculative decoding).

        Each slot's verify row is its last emitted token followed by its k
        host-proposed drafts, so the forward's argmax column j is exactly
        what the j-th sequential :meth:`pool_chunk` step would have
        produced — :func:`repro.serve.speculate.fold_acceptance` then
        emits the longest agreeing prefix plus one correction token and
        rolls ``cache_len`` back over the rejected suffix. Output shape
        matches :meth:`pool_chunk`'s ``(steps, S)`` tokens/valid pair
        (steps = k+1 candidate positions), so the drain loop is unchanged.
        Done slots emit nothing; their junk K/V writes land in their own
        slab/pages (or the null page) exactly like the single-token path's
        frozen decode.
        """
        core = self.core
        if k not in core._verify_fns:
            cfg, ecfg, plans = core.model.cfg, core.ecfg, core.plans

            def run(params, pool: PoolState, drafts, dlen):
                tokens = jnp.concatenate([pool.tok[:, None], drafts], axis=1)
                logits, state = core.model.verify_step(
                    params, tokens, pool.state, pool.cache_len, plans=plans,
                    block_tables=pool.block_tables)
                targets = jnp.argmax(logits[:, :, :cfg.vocab_size],
                                     axis=-1).astype(jnp.int32)   # (S, k+1)
                fold = spec_mod.fold_acceptance(
                    targets, drafts, dlen, done=pool.done, n_gen=pool.n_gen,
                    budget=pool.budget, cache_len=pool.cache_len,
                    max_len=ecfg.max_len, eos_token=ecfg.eos_token)
                toks = jnp.where(fold.valid, targets, ecfg.eos_token)
                new = PoolState(state=state, tok=fold.tok,
                                cache_len=fold.cache_len, done=fold.done,
                                n_gen=fold.n_gen, budget=pool.budget,
                                block_tables=pool.block_tables)
                return new, toks.astype(jnp.int32).T, fold.valid.T

            core._verify_fns[k] = jax.jit(run, donate_argnums=1)
        return core._verify_fns[k]

    def build_drafts(self, sch: sched_mod.Scheduler, k: int
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """Host-side draft proposal for every live slot (drain boundary).

        Proposes from the slot's host-mirrored prompt+emitted context via
        :func:`repro.serve.speculate.propose_ngram`. Slots without a
        proposable context — free, mid-chunked-prefill, or admitted this
        very boundary (first token still on device in ``pending_first``) —
        get ``dlen = 0``, which the fold degrades to an ordinary
        single-token step.
        """
        drafts = np.zeros((sch.n_slots, k), np.int32)
        dlen = np.zeros((sch.n_slots,), np.int32)
        for slot, req in sch.active.items():
            if req.status != sched_mod.DECODING or not req.tokens:
                continue
            ctx = np.concatenate([np.asarray(req.prompt, np.int32),
                                  np.asarray(req.tokens, np.int32)])
            d = spec_mod.propose_ngram(ctx, k)
            drafts[slot, :d.shape[0]] = d
            dlen[slot] = d.shape[0]
        return drafts, dlen


class Engine:
    """The combined engine: one :class:`EngineCore`, one
    :class:`~repro.serve.pool.PoolManager`, a :class:`PrefillRole` and a
    :class:`DecodeRole` — plus the serve loops that drive them. With
    ``EngineConfig(disaggregate=True)`` (paged pool required) the same
    loop routes work, host syncs, and timing by role and executes the
    scheduler's page handovers; otherwise both roles run as one engine
    with byte-identical behavior to the pre-split code."""

    def __init__(self, model: Model, params: Any, ecfg: EngineConfig):
        self.core = EngineCore(model, params, ecfg)
        self.pools = PoolManager(model, ecfg, self.core._build)
        self.prefill_role = PrefillRole(self.core, self.pools)
        self.decode_role = DecodeRole(self.core, self.pools)
        # ---- layer-2 host tier (DESIGN.md §Tiered KV compression & host
        # parking): the paged pool of the LAST serve() call, kept so idle
        # sessions can be parked between calls, and resume content staged
        # by rid until the scheduler re-admits the session
        self._last_pool: Optional[PoolState] = None
        self._last_spill: Optional[Dict[str, Any]] = None
        self._park_pending: Dict[int, Dict[str, np.ndarray]] = {}
        if ecfg.prompt_pad_multiple and self.core._has_ssm():
            raise ValueError(
                "prompt_pad_multiple requires attention-only models: SSM "
                "recurrences integrate pad tokens (see EngineConfig)")
        if ecfg.speculate_tokens and self.core._has_ssm():
            raise ValueError(
                "speculative decoding requires attention-only models: "
                "recurrent SSM state cannot roll back rejected draft "
                "tokens (docs/SERVING.md)")

    # ----------------------------------------------- shared-core surface
    # The public attribute surface predates the role split; tests, the
    # benchmarks, and the stream driver reach these through the engine.
    @property
    def model(self) -> Model:
        return self.core.model

    @property
    def params(self):
        return self.core.params

    @property
    def mesh(self):
        return self.core.mesh

    @property
    def ecfg(self) -> EngineConfig:
        return self.core.ecfg

    @property
    def plans(self):
        return self.core.plans

    @property
    def last_stats(self) -> Dict[str, Any]:
        return self.core.last_stats

    @last_stats.setter
    def last_stats(self, value: Dict[str, Any]) -> None:
        self.core.last_stats = value

    @property
    def _chunk_prefill_fns(self) -> Dict[Any, Any]:
        return self.core._chunk_prefill_fns

    @property
    def _dense_chunk_prefill_fns(self) -> Dict[Any, Any]:
        return self.core._dense_chunk_prefill_fns

    def _has_ssm(self) -> bool:
        return self.core._has_ssm()

    def _mesh_scope(self):
        return self.core._mesh_scope()

    def init_pool(self, n_slots: int) -> PoolState:
        return self.pools.init_pool(n_slots)

    def init_paged_pool(self, sch: sched_mod.Scheduler
                        ) -> Tuple[PoolState, Dict[str, Any]]:
        return self.pools.init_paged_pool(sch)

    def admit_into_slot(self, pool: PoolState, slot: int,
                        prompt: np.ndarray, max_new_tokens: int
                        ) -> Tuple[PoolState, jax.Array]:
        return self.prefill_role.admit_into_slot(pool, slot, prompt,
                                                 max_new_tokens)

    # ---------------------------------------------------------- one-shot
    def prefill(self, batch: Dict[str, jax.Array]) -> Tuple[jax.Array, Dict]:
        logits, state = self.model.prefill(self.params, batch,
                                           self.ecfg.max_len,
                                           plans=self.plans)
        return logits, state

    def generate(self, batch: Dict[str, jax.Array], n_steps: int,
                 ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
        """Greedy continuation. Returns (tokens (B, <=n_steps), final_state).

        Rows that hit EOS are frozen on-device (EOS fill); the host checks
        the done mask once per ``sync_interval`` chunk and stops early at
        that granularity — never per token.
        """
        with self._mesh_scope():
            return self._generate_impl(batch, n_steps)

    def _generate_impl(self, batch: Dict[str, jax.Array], n_steps: int,
                       ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
        self.last_stats = {"host_syncs": 0, "decode_steps": 0}
        cfg = self.model.cfg
        logits, state = self.prefill(batch)
        prompt_len = batch["tokens"].shape[1]
        if cfg.family != "encdec" and cfg.frontend_len:
            prompt_len += cfg.frontend_len
        cache_len = jnp.asarray(prompt_len, jnp.int32)
        tok = jnp.argmax(logits[:, -1, :cfg.vocab_size], axis=-1)
        done = tok == self.ecfg.eos_token
        out: List[jnp.ndarray] = [tok[:, None]]
        left = n_steps - 1
        while left > 0:
            n = min(self.ecfg.sync_interval, left)
            toks, tok, state, cache_len, done = \
                self.decode_role.decode_chunk(n)(
                    self.params, tok, state, cache_len, done)
            out.append(toks)
            left -= n
            self.last_stats["decode_steps"] += n
            # drain boundary: one explicit host read, then maybe early-exit
            if left > 0 and bool(self.core._fetch(done).all()):
                break
        return jnp.concatenate(out, axis=1), state

    # ------------------------------------------- layer-2 host tier (park)
    def park_request(self, sch: sched_mod.Scheduler, rid: int) -> bytes:
        """Park an active DECODING session to the layer-2 host tier.

        Gathers the contents of every page the session maps (codes AND
        per-page scales, verbatim — lossless at any codec) plus its
        per-slot rows, serializes them with the scheduler residue through
        :mod:`repro.serve.park`, then releases the slot and all device
        resources via :meth:`Scheduler.park`. The returned blob is the
        session; feed it to :meth:`resume_parked` to continue the decode
        as a resume, never a re-prefill. fp16 pools round-trip
        byte-identically (raw-bytes serialization, no recompute)."""
        from repro.models import transformer
        from repro.serve import park as park_mod
        slot = next((s for s, r in sch.active.items() if r.rid == rid), None)
        if slot is None:
            raise KeyError(f"rid {rid} is not active; only resident "
                           f"sessions park")
        req = sch.active[slot]
        if req.status != sched_mod.DECODING:
            raise ValueError(
                "only decoding sessions park — a mid-prefill request has "
                "no emitted token to resume from; requeue it instead")
        if self._last_pool is None:
            raise RuntimeError("park_request follows a serve() call — no "
                               "paged pool state is staged")
        pool, cfg = self._last_pool, self.core.model.cfg
        pages = np.asarray(req.pages, np.int32)
        arrays: Dict[str, Any] = {}
        for gname, gkey, is_paged in transformer.paged_cache_kinds(cfg):
            for name, arr in pool.state["caches"][gname][gkey].items():
                key = f"{gname}/{gkey}/{name}"
                if is_paged:
                    arrays["pages/" + key] = arr[:, pages]
                else:
                    arrays["rows/" + key] = jax.lax.dynamic_slice_in_dim(
                        arr, slot, 1, axis=1)
        meta = {"prompt": [int(t) for t in req.prompt],
                "tokens": [int(t) for t in req.tokens],
                "max_new_tokens": int(req.max_new_tokens),
                "cache_len": int(req.cache_len),
                "n_pages": len(req.pages)}
        blob = park_mod.pack_parked(meta, arrays)
        sch.park(slot)
        self.pools.release(slot)
        return blob

    def resume_parked(self, sch: sched_mod.Scheduler,
                      blob: bytes) -> sched_mod.Request:
        """Re-enter a parked session: rebuild the scheduler residue
        (:meth:`Scheduler.submit_parked`) and stage the page contents so
        the next serve() boundary that admits it scatters them back."""
        from repro.serve import park as park_mod
        meta, arrays = park_mod.unpack_parked(blob)
        req = sch.submit_parked(meta["prompt"], meta["max_new_tokens"],
                                meta["tokens"])
        self._park_pending[req.rid] = arrays
        return req

    def _exec_resume(self, pool: PoolState, rs: sched_mod.ResumeStep,
                     geom: sched_mod.PageGeometry) -> PoolState:
        """Scatter a parked session's staged content into its freshly
        mapped pages and re-arm the slot for decode.

        Only PRIVATE logical pages are written: shared (prefix-matched)
        pages already hold the canonical bytes — at fp16 bit-identical to
        the parked copies, which is what keeps park/resume bit-exact even
        through sharing. Parked pages beyond the new mapping (old growth
        margin) sit past the KV frontier and are dropped; freshly mapped
        pages beyond the parked coverage stay zero until decode writes
        them (scale reset at offset 0 keeps int8 clean)."""
        from repro.models import transformer
        req, slot = rs.req, rs.slot
        arrays = self._park_pending.pop(req.rid)
        cfg = self.core.model.cfg
        n_shared = req.n_shared
        parked_n = next((v.shape[1] for key, v in arrays.items()
                         if key.startswith("pages/")), len(req.pages))
        k = min(parked_n, len(req.pages)) - n_shared
        priv = np.asarray(req.pages[n_shared:n_shared + k], np.int32)
        new_caches: Dict[str, Any] = {}
        for gname, gkey, is_paged in transformer.paged_cache_kinds(cfg):
            leaf = pool.state["caches"][gname][gkey]
            new_leaf = dict(leaf)
            for name, arr in leaf.items():
                if is_paged:
                    src = jnp.asarray(
                        arrays[f"pages/{gname}/{gkey}/{name}"])
                    new_leaf[name] = arr.at[:, priv].set(
                        src[:, n_shared:n_shared + k].astype(arr.dtype))
                else:
                    src = jnp.asarray(arrays[f"rows/{gname}/{gkey}/{name}"])
                    new_leaf[name] = jax.lax.dynamic_update_slice_in_dim(
                        arr, src.astype(arr.dtype), slot, axis=1)
            new_caches.setdefault(gname, {})[gkey] = new_leaf
        return dataclasses.replace(
            pool, state={**pool.state, "caches": new_caches},
            tok=pool.tok.at[slot].set(int(req.tokens[-1])),
            cache_len=pool.cache_len.at[slot].set(req.cache_len),
            done=pool.done.at[slot].set(False),
            n_gen=pool.n_gen.at[slot].set(len(req.tokens)),
            budget=pool.budget.at[slot].set(req.max_new_tokens))

    # -------------------------------------------------------- paged serve
    @staticmethod
    def _owner_role(req: sched_mod.Request) -> str:
        """Which role a request's pool work belongs to: mid-prefill (the
        cursor short of the prompt, or freshly PREFILLING) is prefill-role
        work; everything decoding is decode-role work."""
        if (req.status == sched_mod.PREFILLING
                or 0 <= req.prefill_pos < req.prompt_len):
            return PREFILL_ROLE
        return DECODE_ROLE

    def _serve_paged(self, sch: sched_mod.Scheduler,
                     max_steps: Optional[int] = None) -> ServeReport:
        """Continuous batching over the paged two-tier pool.

        Same drain-boundary discipline as the dense loop (ONE host read per
        chunk — per ROLE when disaggregated); what changes is the boundary
        work: the scheduler plans grow / preempt / restore / admit in
        pages, the engine executes the device copies in plan order and
        uploads the fresh block table, and the decode chunk walks block
        tables instead of slot slabs.

        Disaggregated boundary order: spills -> restores -> admissions and
        prefill chunks (prefill role) -> page handovers (the zero-copy
        ownership flips for this boundary's final chunks) -> decode-view
        block-table upload -> decode/verify chunk (decode role) -> decode
        drain fetch -> prefill drain fetch (pending first tokens, only on
        boundaries that completed a prompt). Outputs are bit-identical to
        the combined loop; only issue order and attribution change.
        """
        core, pools = self.core, self.pools
        pre, dec = self.prefill_role, self.decode_role
        geom = sch.pages
        disagg = self.ecfg.disaggregate or sch.disaggregate
        if disagg and not sch.disaggregate:
            sch.enable_disaggregation()
        if sch.prefix_index is not None and core._has_ssm():
            raise ValueError(
                "prefix sharing requires attention-only models: recurrent "
                "SSM state is per-sequence, not per-page (docs/SERVING.md)")
        if sch.chunk_prefill_tokens is not None and core._has_ssm():
            raise ValueError(
                "chunked prefill requires attention-only models: recurrent "
                "SSM state has no resumable KV prefix (docs/SERVING.md)")
        self.last_stats = {"host_syncs": 0, "decode_steps": 0, "chunks": 0}
        pre_role = dec_role = None
        if disagg:
            self.last_stats["host_syncs_by_role"] = {PREFILL_ROLE: 0,
                                                     DECODE_ROLE: 0}
            self.last_stats["decode_tokens"] = 0
            pre_role, dec_role = PREFILL_ROLE, DECODE_ROLE
        spec_k = self.ecfg.speculate_tokens
        if spec_k:
            self.last_stats.update(speculate_tokens=spec_k,
                                   spec_proposed=0, spec_accepted=0)
        pool, spill = pools.init_paged_pool(sch)
        # how many of the pool's paged leaves the decode forward's layer
        # scan carries in place; on every engine.generate span
        leaves = transformer.pool_leaves(core.model.cfg, pool.state["caches"])
        self.last_stats.update(leaves)
        pending_first: List[Tuple[sched_mod.Request, jax.Array]] = []
        boundary_wall: List[float] = []
        boundary_tokens: List[int] = []
        boundary_decode_wall: List[float] = []
        step_clock = 0
        n = self.ecfg.sync_interval
        p_max = geom.max_pages_per_slot
        while sch.has_work():
            with spans.span("engine.boundary",
                            index=self.last_stats["chunks"]) as bsp:
                syncs0 = self.last_stats["host_syncs"]
                # a speculative boundary advances a slot by up to k+1
                # tokens in its one verify forward, so page growth is
                # planned for k+1
                plan = sch.plan_boundary(
                    chunk_tokens=(spec_k + 1 if spec_k else n),
                    max_len=self.ecfg.max_len)
                for req in plan.rejects:
                    req.finish_step, req.finish_t = step_clock, spans.now()
                # spills FIRST: they read layer-0 pages that restores/admits
                # may reuse later this boundary (functional arrays keep this
                # exact)
                for act in plan.spills:
                    spill = core._timed(
                        "insert", pools.exec_spill, pool, spill, act, p_max,
                        role=self._owner_role(act.req) if disagg else None,
                        kind="spill", rid=act.req.rid)
                for act in plan.restores:
                    role = self._owner_role(act.req) if disagg else None
                    if disagg:
                        pools.claim(act.slot, role)
                    pool = core._timed("insert", pools.exec_restore,
                                       pool, spill, act, p_max, role=role,
                                       kind="restore", rid=act.req.rid)
                # layer-2 resumes BEFORE admissions/prefill chunks: a resumed
                # session's pages were registered in the prefix index at
                # plan time, so their bytes must be resident before any
                # same-boundary matcher's suffix chunk reads them
                for rs in plan.resumes:
                    if disagg:
                        pools.claim(rs.slot, DECODE_ROLE)
                    pool = core._timed("insert", self._exec_resume, pool, rs,
                                       geom, role=dec_role, kind="resume",
                                       rid=rs.req.rid)
                for slot, req in plan.admits:
                    req.admit_step = step_clock
                    if disagg:
                        pools.claim(slot, PREFILL_ROLE)
                    if req.prefill_pos >= 0:
                        continue    # chunked admission: runs via prefill_steps
                    tokens = req.prompt_len - req.prefix_len
                    if req.prefix_len:  # prefix-index hit: suffix-only prefill
                        pool, first = core._timed(
                            "prefill", pre.shared_paged_admit,
                            pool, slot, req, geom, role=pre_role,
                            rid=req.rid, tokens=tokens)
                    else:
                        pool, first = core._timed(
                            "prefill", pre.paged_admit, pool, slot, req,
                            geom, role=pre_role, rid=req.rid, tokens=tokens)
                    req.status = sched_mod.DECODING
                    pending_first.append((req, first))
                # chunk prefills AFTER every copy, in plan order (scheduler's
                # ordering contract); a final chunk arms its slot like an
                # admit
                for step in plan.prefill_steps:
                    pool, first = core._timed(
                        "prefill", pre.exec_prefill_chunk, pool, step, geom,
                        role=pre_role, rid=step.req.rid,
                        tokens=step.n_tokens)
                    if step.final:
                        step.req.status = sched_mod.DECODING
                        pending_first.append((step.req, first))
                # page handover: each request whose prompt completed this
                # boundary moves prefill -> decode by a zero-copy ownership
                # flip; the decode role's table upload below carries its row
                for h in plan.handovers:
                    core._timed("handover", pools.transfer_ownership,
                                h.slot, h.pages, rid=h.req.rid)
                # the boundary's page moves, as one host->device upload; the
                # decode role uploads only the rows it owns (handover is
                # what makes a row appear)
                with spans.span("engine.upload"):
                    pool = dataclasses.replace(pool, block_tables=jnp.asarray(
                        sch.block_table(role=DECODE_ROLE) if disagg
                        else sch.block_table()))
                frontier = {slot: req.cache_len
                            for slot, req in sch.active.items()
                            if req.status == sched_mod.DECODING}
                if spec_k:
                    # one verify forward replaces the sync_interval-step
                    # scan; the boundary still costs exactly one host sync
                    drafts, dlen = dec.build_drafts(sch, spec_k)
                    pool, toks, valid = core._timed(
                        "generate", dec.verify_fn(spec_k), core.params, pool,
                        jnp.asarray(drafts), jnp.asarray(dlen),
                        role=dec_role, **leaves)
                    step_clock += 1
                    self.last_stats["decode_steps"] += 1
                    self.last_stats["spec_proposed"] += int(dlen.sum())
                else:
                    pool, toks, valid = core._timed(
                        "generate", dec.pool_chunk(n), core.params, pool,
                        role=dec_role, **leaves, **walk_counts(
                            frontier.values(), n, sch.n_slots, geom))
                    step_clock += n
                    self.last_stats["decode_steps"] += n
                t_generate = core.last_phase.start
                self.last_stats["chunks"] += 1
                # ---- drain boundary: ONE host sync per role (decode always;
                # prefill only on boundaries that completed a prompt)
                if disagg:
                    toks_h, valid_h, done_h = core._timed(
                        "drain", core._fetch, (toks, valid, pool.done),
                        DECODE_ROLE, role=DECODE_ROLE)
                    boundary_decode_wall.append(
                        core.last_phase.end - t_generate)
                    firsts = []
                    if pending_first:
                        firsts = core._timed(
                            "drain", core._fetch,
                            [f for _, f in pending_first], PREFILL_ROLE,
                            role=PREFILL_ROLE)
                else:
                    toks_h, valid_h, done_h, firsts = core._timed(
                        "drain", core._fetch,
                        (toks, valid, pool.done,
                         [f for _, f in pending_first]))
                t_drain = core.last_phase.end
                with spans.span("engine.emit"):
                    emitted = len(firsts)
                    for (req, _), f in zip(pending_first, firsts):
                        req.tokens.append(int(f))
                        # the first token becomes real only at THIS drain —
                        # the boundary clock has already advanced past the
                        # decode/verify work, so ttft_emit_steps measures
                        # true first-token availability instead of the
                        # admission-time clock (which is 0 for anything
                        # admitted at the first boundary)
                        req.first_step, req.first_t = step_clock, t_drain
                    pending_first.clear()
                    decode = []
                    for slot in sorted(sch.active):
                        req = sch.active[slot]
                        before = len(req.tokens)
                        req.tokens.extend(
                            int(t) for t, v in zip(toks_h[:, slot],
                                                   valid_h[:, slot]) if v)
                        got = len(req.tokens) - before
                        emitted += got
                        if slot in frontier:
                            decode.append((frontier[slot], got))
                        if disagg:
                            self.last_stats["decode_tokens"] += got
                        if spec_k:
                            # a live slot's boundary emission is accepted
                            # drafts + 1 correction token; just-admitted
                            # slots (dlen=0) emit exactly 1 and contribute 0
                            # accepted
                            self.last_stats["spec_accepted"] += max(got - 1,
                                                                    0)
                        # a mid-prefill slot's device done flag is still the
                        # free marker from before its admission — only
                        # DECODING slots can drain
                        if done_h[slot] and req.status != sched_mod.PREFILLING:
                            req.finish_step, req.finish_t = step_clock, t_drain
                            pools.release(slot)
                            sch.complete(slot)
                bsp.args.update(
                    admitted=[req.prompt_len for _, req in plan.admits],
                    prefill_tokens=sum(s.n_tokens
                                       for s in plan.prefill_steps),
                    decode=decode, emitted=emitted,
                    spills=len(plan.spills), restores=len(plan.restores),
                    host_syncs=self.last_stats["host_syncs"] - syncs0)
            boundary_wall.append(bsp.seconds)
            boundary_tokens.append(emitted)
            if max_steps is not None and step_clock >= max_steps:
                break
        self.last_stats["boundary_wall_s"] = boundary_wall
        self.last_stats["boundary_tokens"] = boundary_tokens
        if disagg:
            # decode-role boundary wall: start of engine.generate to the end
            # of the decode role's engine.drain (meaningful under
            # phase_timing, where the prefill phase has blocked before
            # engine.generate opens) — the inter-token clock a decode
            # consumer experiences when prefill runs on its own engine
            self.last_stats["boundary_decode_wall_s"] = boundary_decode_wall
        self._finish_spec_stats()
        # stage the pool for park_request between serve() calls (the next
        # serve() builds a fresh pool — parking is how a still-active
        # session's KV survives the gap)
        self._last_pool, self._last_spill = pool, spill
        stats = dict(self.last_stats)
        stats.update(sch.stats())
        return ServeReport(requests=(sch.drained + list(sch.active.values())
                                     + list(sch.queue)),
                           stats=stats)

    def _finish_spec_stats(self) -> None:
        """Derive the acceptance summary counters once a serve run ends."""
        if "spec_proposed" not in self.last_stats:
            return
        prop = self.last_stats["spec_proposed"]
        acc = self.last_stats["spec_accepted"]
        self.last_stats["spec_rejected"] = prop - acc
        self.last_stats["spec_acceptance_rate"] = (
            acc / prop if prop else 0.0)

    # ------------------------------------------------------------ stream
    def serve(self, requests: Iterable[sched_mod.Request] = (),
              scheduler: Optional[sched_mod.Scheduler] = None, *,
              max_steps: Optional[int] = None) -> ServeReport:
        """Continuous batching over a request stream.

        Loop invariant: between drain boundaries everything is on-device.
        Each iteration (1) admits queued requests into free slots, (2) runs
        one ``sync_interval`` decode chunk over the whole pool, (3) performs
        ONE host sync to read the chunk's tokens + done mask, then frees
        drained slots so the next iteration refills them.
        """
        with self._mesh_scope():
            return self._serve_impl(requests, scheduler, max_steps=max_steps)

    def _serve_impl(self, requests: Iterable[sched_mod.Request] = (),
                    scheduler: Optional[sched_mod.Scheduler] = None, *,
                    max_steps: Optional[int] = None) -> ServeReport:
        sch = scheduler or sched_mod.Scheduler.for_model(
            self.model.cfg, self.ecfg.max_len)
        for req in requests:
            sch.submit_request(req)
        if sch.pages is not None:        # paged two-tier pool
            return self._serve_paged(sch, max_steps)
        if self.ecfg.disaggregate or sch.disaggregate:
            raise ValueError(
                "disaggregated serving requires the paged pool: page "
                "handover moves block-table rows, which the dense "
                "slot-slab pool does not have (DESIGN.md §Disaggregated "
                "serving)")
        chunked = sch.chunk_prefill_tokens is not None
        if chunked and self._has_ssm():
            raise ValueError(
                "chunked prefill requires attention-only models: recurrent "
                "SSM state has no resumable KV prefix (docs/SERVING.md)")
        self.last_stats = {"host_syncs": 0, "decode_steps": 0, "chunks": 0}
        spec_k = self.ecfg.speculate_tokens
        if spec_k:
            self.last_stats.update(speculate_tokens=spec_k,
                                   spec_proposed=0, spec_accepted=0)
        core, pre, dec = self.core, self.prefill_role, self.decode_role
        pool = self.init_pool(sch.n_slots)
        pending_first: List[Tuple[sched_mod.Request, jax.Array]] = []
        boundary_wall: List[float] = []
        boundary_tokens: List[int] = []
        step_clock = 0
        while sch.has_work():
            with spans.span("engine.boundary",
                            index=self.last_stats["chunks"]) as bsp:
                syncs0 = self.last_stats["host_syncs"]
                admits = sch.admit()
                for slot, req in admits:
                    req.admit_step = step_clock
                    if req.prompt_len > self.ecfg.max_len:
                        # reject cleanly: one bad request must not abort the
                        # stream or leak its slot
                        req.finish_step, req.finish_t = step_clock, spans.now()
                        sch.complete(slot, status=sched_mod.REJECTED)
                        continue
                    if chunked:
                        continue    # prefills by chunks via plan_prefill below
                    pool, first = core._timed(
                        "prefill", pre.admit_into_slot,
                        pool, slot, req.prompt, req.max_new_tokens,
                        rid=req.rid, tokens=req.prompt_len)
                    req.status = sched_mod.DECODING
                    pending_first.append((req, first))
                steps = sch.plan_prefill() if chunked else []
                for step in steps:
                    pool, first = core._timed(
                        "prefill", pre.exec_dense_chunk, pool, step,
                        rid=step.req.rid, tokens=step.n_tokens)
                    if step.final:
                        step.req.status = sched_mod.DECODING
                        pending_first.append((step.req, first))
                frontier = {slot: req.cache_len
                            for slot, req in sch.active.items()
                            if req.status == sched_mod.DECODING}
                if spec_k:
                    # one verify forward replaces the sync_interval-step
                    # scan; the boundary still costs exactly one host sync
                    drafts, dlen = dec.build_drafts(sch, spec_k)
                    pool, toks, valid = core._timed(
                        "generate", dec.verify_fn(spec_k), core.params, pool,
                        jnp.asarray(drafts), jnp.asarray(dlen))
                    step_clock += 1
                    self.last_stats["decode_steps"] += 1
                    self.last_stats["spec_proposed"] += int(dlen.sum())
                else:
                    n = self.ecfg.sync_interval
                    pool, toks, valid = core._timed(
                        "generate", dec.pool_chunk(n), core.params, pool)
                    step_clock += n
                    self.last_stats["decode_steps"] += n
                self.last_stats["chunks"] += 1
                # ---- drain boundary: the single host sync of this iteration
                toks_h, valid_h, done_h, firsts = core._timed(
                    "drain", core._fetch,
                    (toks, valid, pool.done, [f for _, f in pending_first]))
                t_drain = core.last_phase.end
                with spans.span("engine.emit"):
                    emitted = len(firsts)
                    for (req, _), f in zip(pending_first, firsts):
                        req.tokens.append(int(f))
                        # the first token becomes real only at THIS drain —
                        # the boundary clock has already advanced past the
                        # decode/verify work, so ttft_emit_steps measures
                        # true first-token availability instead of the
                        # admission-time clock (which is 0 for anything
                        # admitted at the first boundary)
                        req.first_step, req.first_t = step_clock, t_drain
                    pending_first.clear()
                    decode = []
                    for slot in sorted(sch.active):
                        req = sch.active[slot]
                        before = len(req.tokens)
                        req.tokens.extend(
                            int(t) for t, v in zip(toks_h[:, slot],
                                                   valid_h[:, slot]) if v)
                        got = len(req.tokens) - before
                        emitted += got
                        if slot in frontier:
                            decode.append((frontier[slot], got))
                        if spec_k:
                            # a live slot's boundary emission is accepted
                            # drafts + 1 correction token; just-admitted
                            # slots (dlen=0) emit exactly 1 and contribute 0
                            # accepted
                            self.last_stats["spec_accepted"] += max(got - 1,
                                                                    0)
                        # mid-prefill slots keep their stale free-marker done
                        # flag; only DECODING slots can drain
                        if done_h[slot] and req.status != sched_mod.PREFILLING:
                            req.finish_step, req.finish_t = step_clock, t_drain
                            sch.complete(slot)
                bsp.args.update(
                    admitted=[req.prompt_len for _, req in admits
                              if req.status != sched_mod.REJECTED],
                    prefill_tokens=sum(s.n_tokens for s in steps),
                    decode=decode, emitted=emitted, spills=0, restores=0,
                    host_syncs=self.last_stats["host_syncs"] - syncs0)
            boundary_wall.append(bsp.seconds)
            boundary_tokens.append(emitted)
            if max_steps is not None and step_clock >= max_steps:
                break
        self.last_stats["boundary_wall_s"] = boundary_wall
        self.last_stats["boundary_tokens"] = boundary_tokens
        self._finish_spec_stats()
        stats = dict(self.last_stats)
        stats.update(sch.stats())
        return ServeReport(requests=sch.drained + list(sch.active.values()),
                           stats=stats)
