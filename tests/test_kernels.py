"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp oracles.

Kernels execute in interpret mode on CPU (the kernel body runs in Python);
the oracles in kernels/ref.py are the ground truth.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tiling
from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.matmul3d import matmul3d
from repro.kernels.mamba_scan import mamba_scan

KEY = jax.random.PRNGKey(42)


def _k(i):
    return jax.random.fold_in(KEY, i)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------------- matmul

@pytest.mark.parametrize("m,k,n", [
    (128, 128, 128),       # single block
    (256, 384, 512),       # multi-block all dims
    (512, 128, 256),       # deep M
    (128, 512, 128),       # deep K (accumulator carry)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_sweep(m, k, n, dtype):
    a = jax.random.normal(_k(1), (m, k), jnp.float32).astype(dtype)
    b = jax.random.normal(_k(2), (k, n), jnp.float32).astype(dtype)
    plan = tiling.MatmulPlan(bm=128, bk=128, bn=128)
    got = matmul3d(a, b, plan=plan, out_dtype=jnp.float32, interpret=True)
    want = ref.matmul_ref(a, b, jnp.float32)
    np.testing.assert_allclose(got, want, **_tol(dtype))


def test_matmul_block_shapes_irrelevant_to_result():
    """The paper's tiling changes traffic, never the numerics."""
    a = jax.random.normal(_k(3), (512, 512), jnp.float32)
    b = jax.random.normal(_k(4), (512, 512), jnp.float32)
    outs = []
    for bm, bk, bn in [(128, 128, 128), (256, 128, 256), (512, 256, 128)]:
        plan = tiling.MatmulPlan(bm, bk, bn)
        outs.append(matmul3d(a, b, plan=plan, out_dtype=jnp.float32,
                             interpret=True))
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=1e-4, atol=1e-4)


def test_matmul_wrapper_pads_and_crops():
    """ops.matmul handles non-block-multiple shapes via pad+crop."""
    a = jax.random.normal(_k(5), (200, 300), jnp.float32)
    b = jax.random.normal(_k(6), (300, 100), jnp.float32)
    got = ops.matmul(a, b, plan=tiling.MatmulPlan(128, 128, 128), impl="pallas")
    want = ref.matmul_ref(a, b)
    assert got.shape == (200, 100)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------- attention

@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])   # MHA/GQA/MQA
@pytest.mark.parametrize("causal,window", [
    (True, None), (True, 64), (False, None),
])
def test_attention_sweep(hq, hkv, causal, window):
    b, s, d = 2, 256, 64
    q = jax.random.normal(_k(7), (b, hq, s, d), jnp.float32)
    k = jax.random.normal(_k(8), (b, hkv, s, d), jnp.float32)
    v = jax.random.normal(_k(9), (b, hkv, s, d), jnp.float32)
    plan = tiling.AttentionPlan(block_q=128, block_kv=128)
    got = flash_attention(q, k, v, plan=plan, causal=causal, window=window,
                          interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("sq,skv,offset,causal", [
    (37, 37, 0, True),        # shorter than one 8-row tile
    (200, 330, 0, True),      # prefill into a deeper cache (engine shape)
    (100, 300, 100, True),    # resumed at a static offset
    (70, 90, 0, False),       # padded keys must be masked, not attended
])
def test_attention_entry_pads_unaligned_lengths(sq, skv, offset, causal):
    """``ops.attention`` pads both sequences to block multiples and masks
    the padded keys (``kv_len``), so the kernel keeps aligned blocks at any
    prompt length and still matches the oracle."""
    q = jax.random.normal(_k(20), (1, 4, sq, 64), jnp.float32)
    k = jax.random.normal(_k(21), (1, 2, skv, 64), jnp.float32)
    v = jax.random.normal(_k(22), (1, 2, skv, 64), jnp.float32)
    plan = tiling.AttentionPlan(block_q=64, block_kv=128)
    got = ops.attention(q, k, v, causal=causal, q_offset=offset, plan=plan,
                        impl="pallas")
    want = ref.attention_ref(q, k, v, causal=causal, q_offset=offset)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_attention_dtypes(dtype):
    b, h, s, d = 1, 2, 128, 64
    q = jax.random.normal(_k(10), (b, h, s, d), jnp.float32).astype(dtype)
    k = jax.random.normal(_k(11), (b, h, s, d), jnp.float32).astype(dtype)
    v = jax.random.normal(_k(12), (b, h, s, d), jnp.float32).astype(dtype)
    got = flash_attention(q, k, v, plan=tiling.AttentionPlan(64, 64),
                          causal=True, interpret=True)
    want = ref.attention_ref(q, k, v, causal=True)
    assert got.dtype == dtype
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), **_tol(dtype))


def test_attention_q_offset_decode_semantics():
    """q_offset must reproduce 'query block at absolute position' masking —
    the decode/chunked-prefill contract."""
    b, h, s_kv, d = 1, 2, 256, 64
    sq, off = 128, 128
    q_full = jax.random.normal(_k(13), (b, h, s_kv, d), jnp.float32)
    k = jax.random.normal(_k(14), (b, h, s_kv, d), jnp.float32)
    v = jax.random.normal(_k(15), (b, h, s_kv, d), jnp.float32)
    full = ref.attention_ref(q_full, k, v, causal=True)
    part = flash_attention(q_full[:, :, off:off + sq], k, v,
                           plan=tiling.AttentionPlan(64, 64), causal=True,
                           q_offset=off, interpret=True)
    np.testing.assert_allclose(part, full[:, :, off:off + sq],
                               rtol=2e-4, atol=2e-4)


def test_attention_block_size_invariance():
    b, h, s, d = 1, 2, 256, 64
    q = jax.random.normal(_k(16), (b, h, s, d), jnp.float32)
    k = jax.random.normal(_k(17), (b, h, s, d), jnp.float32)
    v = jax.random.normal(_k(18), (b, h, s, d), jnp.float32)
    outs = [flash_attention(q, k, v, plan=tiling.AttentionPlan(bq, bkv),
                            causal=True, window=96, interpret=True)
            for bq, bkv in [(64, 64), (128, 64), (64, 128), (256, 256)]]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=2e-4, atol=2e-4)


def test_attention_blockwise_ref_matches_direct():
    """The XLA long-sequence path (blockwise oracle) == direct softmax."""
    b, h, s, d = 2, 2, 320, 32
    q = jax.random.normal(_k(19), (b, h, s, d), jnp.float32)
    k = jax.random.normal(_k(20), (b, h, s, d), jnp.float32)
    v = jax.random.normal(_k(21), (b, h, s, d), jnp.float32)
    for window in (None, 100):
        got = ref.attention_ref_blockwise(q, k, v, causal=True, window=window,
                                          block_q=64, block_kv=64)
        want = ref.attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------- mamba scan

@pytest.mark.parametrize("length,chunk", [(64, 16), (128, 64), (128, 128)])
@pytest.mark.parametrize("di,ds", [(128, 16), (256, 8)])
def test_mamba_scan_sweep(length, chunk, di, ds):
    b = 2
    x = jax.random.normal(_k(22), (b, length, di), jnp.float32) * 0.1
    dt = jax.nn.softplus(jax.random.normal(_k(23), (b, length, di))) * 0.1
    a = -jnp.exp(jax.random.normal(_k(24), (di, ds)) * 0.1)
    bb = jax.random.normal(_k(25), (b, length, ds)) * 0.1
    c = jax.random.normal(_k(26), (b, length, ds)) * 0.1
    d = jnp.ones((di,))
    got = mamba_scan(x, dt, a, bb, c, d, plan=tiling.ScanChunkPlan(chunk),
                     interpret=True)
    want = ref.selective_scan_ref(x, dt, a, bb, c, d)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_mamba_scan_chunk_invariance():
    """State carried across chunk boundaries == monolithic scan (the paper's
    resident-tile rule applied to the SSM state)."""
    b, length, di, ds = 1, 128, 128, 16
    x = jax.random.normal(_k(27), (b, length, di)) * 0.1
    dt = jax.nn.softplus(jax.random.normal(_k(28), (b, length, di))) * 0.1
    a = -jnp.exp(jax.random.normal(_k(29), (di, ds)) * 0.1)
    bb = jax.random.normal(_k(30), (b, length, ds)) * 0.1
    c = jax.random.normal(_k(31), (b, length, ds)) * 0.1
    d = jnp.ones((di,))
    outs = [mamba_scan(x, dt, a, bb, c, d, plan=tiling.ScanChunkPlan(ch),
                       interpret=True) for ch in (16, 32, 128)]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=2e-4, atol=2e-4)


def test_selective_scan_decode_state_carry():
    """Split scan (prefix + carried h0) == full scan — the decode contract."""
    b, length, di, ds = 2, 64, 64, 16
    x = jax.random.normal(_k(32), (b, length, di)) * 0.1
    dt = jax.nn.softplus(jax.random.normal(_k(33), (b, length, di))) * 0.1
    a = -jnp.exp(jax.random.normal(_k(34), (di, ds)) * 0.1)
    bb = jax.random.normal(_k(35), (b, length, ds)) * 0.1
    c = jax.random.normal(_k(36), (b, length, ds)) * 0.1
    d = jnp.ones((di,))
    full = ref.selective_scan_ref(x, dt, a, bb, c, d)
    half = length // 2
    y1, h = ref.selective_scan_ref(x[:, :half], dt[:, :half], a, bb[:, :half],
                                   c[:, :half], d, return_state=True)
    y2 = ref.selective_scan_ref(x[:, half:], dt[:, half:], a, bb[:, half:],
                                c[:, half:], d, h0=h)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], axis=1), full,
                               rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------ ops dispatch

def test_ops_dispatch_ref_on_cpu():
    """impl='auto' uses the oracle on CPU (Pallas only via interpret)."""
    a = jax.random.normal(_k(37), (64, 64))
    b = jax.random.normal(_k(38), (64, 64))
    np.testing.assert_allclose(ops.matmul(a, b), ref.matmul_ref(a, b),
                               rtol=1e-5, atol=1e-5)


def test_ops_attention_grad_flows():
    """The model's attention path must be differentiable (training dep)."""
    q = jax.random.normal(_k(39), (1, 2, 64, 32))
    k = jax.random.normal(_k(40), (1, 2, 64, 32))
    v = jax.random.normal(_k(41), (1, 2, 64, 32))

    def f(q):
        return ops.attention(q, k, v, causal=True).sum()

    g = jax.grad(f)(q)
    assert bool(jnp.isfinite(g).all()) and float(jnp.abs(g).max()) > 0


@pytest.mark.parametrize("mesh", ["2x2", "1x4"])
def test_kernels_split_over_slots_and_heads_under_a_mesh(mesh):
    """Under a data x model mesh the Pallas flash kernel and page walk run
    per shard on that shard's slots and heads, gather nothing, and equal
    the unsharded result (tests/over_heads_check.py, in a child python on
    four forced host devices)."""
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(here), "src")
                         + os.pathsep + env.get("PYTHONPATH", "")
                         ).rstrip(os.pathsep)
    proc = subprocess.run(
        [sys.executable, os.path.join(here, "over_heads_check.py"), mesh],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    assert "OVER_HEADS_OK" in proc.stdout
