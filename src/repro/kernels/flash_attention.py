"""Blockwise (flash) attention kernel with causal / sliding-window masking.

Capacity-aware the MemPool way: the (block_q, block_kv) working set — Q block,
double-buffered K/V blocks, f32 accumulator and running softmax stats — is
sized by :func:`repro.core.tiling.plan_attention` to fill the VMEM budget.
GQA is handled in the index map (Hq query heads read Hq/Hkv-strided KV heads),
so KV blocks are fetched once per query-head group member without materializing
`repeat`ed KV in HBM.

Blocks that are fully masked (beyond the causal diagonal, or behind the
sliding window) are skipped with ``pl.when`` on a program-id predicate — the
TPU analogue of MemPool skipping empty memory phases.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.tiling import AttentionPlan

_NEG_INF = float("-inf")
_STATS_LANES = 128  # stats scratch is (bq, 128) for TPU lane alignment


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale: float, block_q: int, block_kv: int, n_kv: int,
                 causal: bool, window: int | None, q_offset: int,
                 kv_len: int | None):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Static-per-step visibility: skip fully masked K/V blocks.
    q_lo = iq * block_q + q_offset
    q_hi = q_lo + block_q - 1
    k_lo = ik * block_kv
    k_hi = k_lo + block_kv - 1
    visible = jnp.bool_(True)
    if causal:
        visible &= k_lo <= q_hi
    if window is not None:
        visible &= k_hi > q_lo - window
    if kv_len is not None:
        visible &= k_lo < kv_len

    @pl.when(visible)
    def _update():
        q = q_ref[0, 0].astype(jnp.float32)           # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)           # (bkv, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        mask = jnp.ones_like(s, dtype=bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        if kv_len is not None:
            mask &= kpos < kv_len
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, :1]                          # (bq, 1)
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        # Guards: fully masked rows keep m == -inf; exp must not produce NaN.
        alpha = jnp.where(m_prev > _NEG_INF, jnp.exp(m_prev - m_new), 0.0)
        p = jnp.where(s > _NEG_INF, jnp.exp(s - m_new), 0.0)
        l_new = alpha * l_prev + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == n_kv - 1)
    def _flush():
        l = l_ref[:, :1]
        out = acc_ref[...] / jnp.maximum(l, 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "plan", "causal", "window", "scale", "q_offset", "kv_len", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    plan: AttentionPlan,
                    causal: bool = True,
                    window: int | None = None,
                    scale: float | None = None,
                    q_offset: int = 0,
                    kv_len: int | None = None,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Hq, Sq, D); k: (B, Hkv, Skv, D); v: (B, Hkv, Skv, Dv).
    Dv may differ from D (MLA decompressed heads). Sq % bq == Skv % bkv == 0.
    ``kv_len`` masks key positions at or past it (right-padded K/V).
    """
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    dv = v.shape[-1]
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    bq, bkv = min(plan.block_q, sq), min(plan.block_kv, skv)
    assert sq % bq == 0 and skv % bkv == 0, (sq, skv, bq, bkv)
    n_kv = skv // bkv
    grid = (b, hq, sq // bq, n_kv)

    kernel = functools.partial(
        _attn_kernel, scale=scale, block_q=bq, block_kv=bkv, n_kv=n_kv,
        causal=causal, window=window, q_offset=q_offset, kv_len=kv_len)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda ib, ih, iq, ik, g=group: (ib, ih // g, ik, 0)),
            pl.BlockSpec((1, 1, bkv, dv),
                         lambda ib, ih, iq, ik, g=group: (ib, ih // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dv),
                               lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, _STATS_LANES), jnp.float32),
            pltpu.VMEM((bq, _STATS_LANES), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
