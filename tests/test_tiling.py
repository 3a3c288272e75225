"""Capacity-aware planner invariants (unit + hypothesis property tests)."""

import pytest

hypothesis = pytest.importorskip("hypothesis")
st = pytest.importorskip("hypothesis.strategies")

from repro.core import tiling
from repro.core.hw_profiles import MiB, TPU_V5E, TpuProfile


def test_plan_matmul_respects_budget_and_alignment():
    plan = tiling.plan_matmul(4096, 4096, 4096)
    assert plan.bm % 128 == 0 and plan.bk % 128 == 0 and plan.bn % 128 == 0
    assert plan.vmem_bytes() <= TPU_V5E.vmem_limit_bytes * 0.75


def test_tpu_plans_fit_the_scoped_vmem_limit():
    """Plans budget against the 16 MiB a kernel may claim, not the physical
    128 MiB: 2048x2048 attention blocks and 4096x1024x4096 matmul blocks,
    planned against 128 MiB, were refused by the TPU compiler."""
    from repro.core import planner
    attn = planner.attention_kernel_plan(4096, 4096, 128, in_bytes=2)
    assert attn.vmem_bytes(128) <= TPU_V5E.vmem_limit_bytes * 0.5
    assert (attn.block_q, attn.block_kv) == (2048, 512)
    mm = planner.matmul_kernel_plan(4096, 4096, 4096, in_bytes=2)
    assert mm.vmem_bytes() <= TPU_V5E.vmem_limit_bytes * 0.75
    assert (mm.bm, mm.bk, mm.bn) == (1024, 512, 1024)


def test_clamped_attention_blocks_stay_aligned():
    """Clamping never shrinks a block to a divisor of an unaligned length:
    blocks stay multiples of the 8-row tile (the entry pads instead)."""
    from repro.core import planner
    plan = planner.attention_kernel_plan(1999, 4128, 128, in_bytes=2)
    assert plan.block_q % 8 == 0 and plan.block_kv % 8 == 0
    assert plan.block_q >= 512 and plan.block_kv >= 512
    small = planner.attention_kernel_plan(37, 48, 128, in_bytes=2)
    assert (small.block_q, small.block_kv) == (40, 48)


def test_plan_matmul_grows_with_capacity():
    """The paper's law: more scratchpad => bigger tiles => fewer reloads."""
    small = TpuProfile(name="small", peak_flops_bf16=1, hbm_bw=1, hbm_bytes=1,
                       ici_link_bw=1, ici_links=1, vmem_bytes=8 * MiB,
                       vmem_limit_bytes=8 * MiB)
    big = TpuProfile(name="big", peak_flops_bf16=1, hbm_bw=1, hbm_bytes=1,
                     ici_link_bw=1, ici_links=1, vmem_bytes=128 * MiB,
                     vmem_limit_bytes=128 * MiB)
    p_small = tiling.plan_matmul(8192, 8192, 8192, profile=small)
    p_big = tiling.plan_matmul(8192, 8192, 8192, profile=big)
    assert p_big.bm * p_big.bn > p_small.bm * p_small.bn
    t_small = p_small.hbm_traffic_bytes(8192, 8192, 8192)
    t_big = p_big.hbm_traffic_bytes(8192, 8192, 8192)
    assert t_big < t_small


def test_matmul_arithmetic_intensity_increases_with_blocks():
    lo = tiling.MatmulPlan(128, 128, 128)
    hi = tiling.MatmulPlan(512, 128, 512)
    m = k = n = 8192
    assert hi.arithmetic_intensity(m, k, n) > lo.arithmetic_intensity(m, k, n)


@hypothesis.given(
    m=st.integers(1, 65536), k=st.integers(1, 65536), n=st.integers(1, 65536),
    vmem_mib=st.sampled_from([16, 32, 64, 128]))
@hypothesis.settings(max_examples=80, deadline=None)
def test_plan_matmul_properties(m, k, n, vmem_mib):
    """For ANY problem: blocks are 128-aligned, fit the budget, and never
    exceed the (aligned-up) problem dims."""
    prof = TpuProfile(name="p", peak_flops_bf16=1, hbm_bw=1, hbm_bytes=1,
                      ici_link_bw=1, ici_links=1,
                      vmem_bytes=128 * MiB, vmem_limit_bytes=vmem_mib * MiB)
    plan = tiling.plan_matmul(m, k, n, profile=prof)
    assert plan.bm % 128 == 0 and plan.bk % 128 == 0 and plan.bn % 128 == 0
    assert plan.vmem_bytes() <= prof.vmem_limit_bytes * 0.75
    assert plan.bm <= max(128, -(-m // 128) * 128)
    assert plan.bn <= max(128, -(-n // 128) * 128)
    assert plan.bk <= max(128, -(-k // 128) * 128)


@hypothesis.given(sq=st.integers(1, 1 << 20), skv=st.integers(1, 1 << 20),
                  hd=st.sampled_from([64, 128, 192, 256]))
@hypothesis.settings(max_examples=80, deadline=None)
def test_plan_attention_properties(sq, skv, hd):
    plan = tiling.plan_attention(sq, skv, hd)
    assert plan.block_q >= 128 and plan.block_kv >= 128
    assert plan.vmem_bytes(hd) <= TPU_V5E.vmem_limit_bytes * 0.5
    assert plan.block_q <= 2048 and plan.block_kv <= 2048


@hypothesis.given(seq=st.integers(8, 1 << 20),
                  di=st.sampled_from([1024, 4096, 8192, 16384]),
                  ds=st.sampled_from([8, 16, 32]))
@hypothesis.settings(max_examples=60, deadline=None)
def test_plan_scan_chunk_properties(seq, di, ds):
    plan = tiling.plan_scan_chunk(seq, di, ds)
    assert plan.chunk >= 8
    assert plan.vmem_bytes(di, ds) <= TPU_V5E.vmem_limit_bytes * 0.5


@hypothesis.given(spm_kib=st.integers(64, 64 * 1024))
@hypothesis.settings(max_examples=60, deadline=None)
def test_mempool_tile_monotone_in_capacity(spm_kib):
    """Tile size is monotone nondecreasing in SPM bytes & always fits."""
    t = tiling.mempool_tile_size(spm_kib * 1024)
    t2 = tiling.mempool_tile_size(spm_kib * 2 * 1024)
    assert t2 >= t
    assert tiling.MEMPOOL_RESIDENT_TILES * 4 * t * t <= spm_kib * 1024
    assert t % tiling.MEMPOOL_TILE_ALIGN == 0


def test_offchip_traffic_decreases_with_tile():
    m = 326400
    tr = [tiling.offchip_traffic_bytes(m, t) for t in (256, 384, 544, 800)]
    assert tr == sorted(tr, reverse=True)
