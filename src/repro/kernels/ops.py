"""Public, planner-driven entry points for the kernel package.

Models call these; they dispatch to the Pallas kernel (interpret mode on CPU,
compiled on TPU) or to the pure-jnp oracle. The dispatch default — oracle on
CPU, Pallas on TPU — keeps tests fast while exercising identical math; kernel
sweeps in tests/test_kernels.py pin ``impl="pallas"`` to validate the kernels
themselves.

Block plans come from the LRU plan cache in :mod:`repro.core.planner`, keyed
on (current hardware target, shapes, dtypes): repeated calls with the same
problem reuse the same plan object instead of re-planning, and callers that
hold a :class:`~repro.core.planner.KernelPlans` (models/serving thread them
from build time) pass it via ``plan=``; it is clamped to the concrete shapes
by the planner's shared pad/clamp helpers.
"""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

from repro.core import planner, tiling
from repro.distributed import sharding as shd
from repro.kernels import ref
from repro import runtime_flags
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.matmul3d import matmul3d as _matmul
from repro.kernels.mamba_scan import mamba_scan as _scan

Impl = Literal["auto", "pallas", "ref"]


def on_tpu() -> bool:
    """THE kernel-route switch: compiled Pallas kernels on a TPU backend,
    the jnp oracles (or interpret mode) elsewhere. Every ``impl="auto"``
    dispatch asks here, so a compile-only test can steer all of them."""
    return jax.default_backend() == "tpu"


def _use_pallas(impl: Impl) -> bool:
    if impl == "auto":
        return on_tpu()
    return impl == "pallas"


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def matmul(a: jax.Array, b: jax.Array, *,
           plan: tiling.MatmulPlan | None = None,
           out_dtype: jnp.dtype | None = None,
           impl: Impl = "auto") -> jax.Array:
    """Capacity-aware tiled matmul; pads to block multiples then crops."""
    if not _use_pallas(impl):
        return ref.matmul_ref(a, b, out_dtype)
    m, k = a.shape
    _, n = b.shape
    if plan is None:
        eff = planner.matmul_kernel_plan(m, k, n, in_bytes=a.dtype.itemsize)
    else:
        eff = planner.clamp_matmul_plan(plan, m, k, n)
    ap = _pad_to(_pad_to(a, 0, eff.bm), 1, eff.bk)
    bp = _pad_to(_pad_to(b, 0, eff.bk), 1, eff.bn)
    out = _matmul(ap, bp, plan=eff, out_dtype=out_dtype or a.dtype,
                  interpret=not on_tpu())
    return out[:m, :n]


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True,
              window: int | None = None,
              scale: float | None = None,
              q_offset: int | jax.Array = 0,
              plan: tiling.AttentionPlan | None = None,
              impl: Impl = "auto") -> jax.Array:
    """Blockwise attention; q (B,Hq,Sq,D), k/v (B,Hkv,Skv,D).

    ``q_offset`` may be a traced int32 scalar (chunked prefill resumes at
    a runtime cursor): the offset only enters the mask arithmetic of the
    jnp reference paths, so a traced offset computes the exact same HLO as
    a static one. The Pallas kernel needs a static grid offset, so traced
    offsets always take the reference path.
    """
    if (not _use_pallas(impl) or isinstance(q_offset, jax.Array)
            or not shd.kernel_heads_shard(k.shape[1])):
        # long sequences take the blockwise XLA path (bounded transients);
        # short ones take the direct softmax (cheaper compile, exact grads).
        # Cost-mode lowering (dry-run) unrolls the block scans with capped
        # trip counts so HloCostAnalysis sees every block body.
        if runtime_flags.cost_mode():
            blk = runtime_flags.cost_attn_block()
            if q.shape[2] * k.shape[2] > blk * blk:
                return ref.attention_ref_blockwise(
                    q, k, v, causal=causal, window=window, scale=scale,
                    q_offset=q_offset, block_q=blk, block_kv=blk, unroll=True)
            return ref.attention_ref(q, k, v, causal=causal, window=window,
                                     scale=scale, q_offset=q_offset)
        if q.shape[2] * k.shape[2] > 4096 * 4096:
            return ref.attention_ref_blockwise(
                q, k, v, causal=causal, window=window, scale=scale,
                q_offset=q_offset)
        return ref.attention_ref(q, k, v, causal=causal, window=window,
                                 scale=scale, q_offset=q_offset)
    _, _, sq, d = q.shape
    skv = k.shape[2]
    if plan is None:
        eff = planner.attention_kernel_plan(sq, skv, d,
                                            in_bytes=q.dtype.itemsize)
    else:
        eff = planner.clamp_attention_plan(plan, sq, skv)
    # pad both sequences to block multiples: padded query rows are cropped,
    # padded key columns are masked by ``kv_len``
    qp = _pad_to(q, 2, eff.block_q)
    kp, vp = _pad_to(k, 2, eff.block_kv), _pad_to(v, 2, eff.block_kv)
    flash = functools.partial(
        _flash, plan=eff, causal=causal, window=window, scale=scale,
        q_offset=q_offset, kv_len=skv if kp.shape[2] != skv else None,
        interpret=not on_tpu())
    out = shd.over_heads(flash, ("bh", "bh", "bh"))(qp, kp, vp)
    return out[:, :, :sq]


def selective_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                   c: jax.Array, d: jax.Array, *,
                   plan: tiling.ScanChunkPlan | None = None,
                   h0: jax.Array | None = None,
                   return_state: bool = False,
                   impl: Impl = "auto"):
    """Mamba-1 selective scan (see ref.selective_scan_ref for shapes)."""
    if not _use_pallas(impl) or return_state or h0 is not None:
        # decode path (carried state) stays on the jnp oracle
        return ref.selective_scan_ref(x, dt, a, b, c, d, h0=h0,
                                      return_state=return_state)
    bsz, length, di = x.shape
    ds = a.shape[1]
    if plan is None:
        eff = planner.scan_kernel_plan(length, di, ds)
    else:
        eff = planner.clamp_scan_plan(plan, length)
    bd = 128
    while di % bd:
        bd //= 2
    return _scan(x, dt, a, b, c, d, plan=eff, block_d=max(bd, 1),
                 interpret=not on_tpu())
