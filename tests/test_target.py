"""HardwareTarget registry, CapacityPartition invariants, plan-cache hits."""

import pytest

from repro.core import planner, tiling
from repro.core.hw_profiles import MiB, TPU_V5E
from repro.core.target import (CapacityPartition, available_targets,
                               get_target, mempool_target, set_target,
                               tpu_target, use_target)


@pytest.fixture(autouse=True)
def _clean_target():
    set_target(None)
    yield
    set_target(None)


# ------------------------------------------------------------------ registry

def test_default_target_is_v5e():
    t = get_target()
    assert t.name == "tpu-v5e" and t.kind == "tpu"
    assert t.profile is TPU_V5E
    assert t.hierarchy.names == ("vmem", "hbm", "ici", "dci")
    assert t.scratchpad_bytes == TPU_V5E.vmem_bytes
    # plans budget against the scoped limit one kernel may claim, not the
    # physical 128 MiB (a 128 MiB plan is refused by the TPU compiler)
    assert t.kernel_scratchpad_bytes == TPU_V5E.vmem_limit_bytes == 16 * MiB
    assert t.partition().capacity_bytes == 16 * MiB


def test_registry_has_all_profiles():
    names = available_targets()
    assert "tpu-v5e" in names and "tpu-v5p" in names
    assert len(available_targets(kind="mempool")) == 8


def test_get_by_name_and_normalization():
    # canonical profile spelling and normalized spelling both resolve
    assert get_target("MemPool-3D_4MiB") is get_target("mempool-3d-4mib")
    assert get_target("mempool-3d-4mib").kind == "mempool"
    assert get_target("mempool-3d-4mib").hierarchy.names == (
        "tile", "group", "cluster", "offchip")


def test_unknown_target_raises_with_choices():
    with pytest.raises(KeyError, match="tpu-v5e"):
        get_target("tpu-v9000")


def test_device_kind_maps_to_a_registered_target():
    from repro.core.target import target_for_device_kind
    assert target_for_device_kind("TPU v5 lite").name == "tpu-v5e"
    assert target_for_device_kind("TPU v5").name == "tpu-v5p"
    # an unknown chip is an error, never the v5e default
    with pytest.raises(KeyError, match="TPU v5 lite"):
        target_for_device_kind("TPU v9")
    with pytest.raises(KeyError):
        target_for_device_kind("cpu")


def test_set_target_and_restore():
    prev = set_target("tpu-v5p")
    assert prev is None
    assert get_target().name == "tpu-v5p"
    set_target(None)
    assert get_target().name == "tpu-v5e"


def test_use_target_context():
    with use_target("mempool-2d-1mib") as t:
        assert t.kind == "mempool"
        assert get_target() is t
    assert get_target().name == "tpu-v5e"


def test_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_TARGET", "tpu-v5p")
    assert get_target().name == "tpu-v5p"
    # explicit set_target wins over the environment
    set_target("tpu-v5e")
    assert get_target().name == "tpu-v5e"


# --------------------------------------------------------- CapacityPartition

def test_partition_budget_within_capacity():
    part = CapacityPartition(capacity_bytes=128 * MiB, fraction=0.75)
    assert part.budget_bytes <= part.capacity_bytes
    assert part.budget_bytes == int(128 * MiB * 0.75)


def test_partition_required_bytes_accounting():
    part = CapacityPartition(capacity_bytes=1000, fraction=1.0, n_buffers=2)
    # 2 copies of each streamed byte + resident
    assert part.required_bytes(300, 100) == 700
    assert part.fits(300, 100) and not part.fits(500, 100)


def test_partition_margin_floor():
    # single-buffered flow keeps the db margin; full double-buffering
    # subsumes it (mult = max(n_buffers, 1 + margin))
    single = CapacityPartition(1000, n_buffers=1, db_margin=0.125)
    double = CapacityPartition(1000, n_buffers=2, db_margin=0.125)
    assert single.streamed_multiplier == 1.125
    assert double.streamed_multiplier == 2.0


def test_partition_validation():
    with pytest.raises(ValueError):
        CapacityPartition(1000, fraction=0.0)
    with pytest.raises(ValueError):
        CapacityPartition(1000, n_buffers=0)


def test_double_buffering_shrinks_blocks():
    """n_buffers=2 halves the streamed budget -> strictly smaller blocks
    when capacity binds."""
    cap = 16 * MiB
    p1 = CapacityPartition(cap, fraction=0.75, n_buffers=1)
    p2 = CapacityPartition(cap, fraction=0.75, n_buffers=2)
    m1 = tiling.plan_matmul(8192, 8192, 8192, partition=p1)
    m2 = tiling.plan_matmul(8192, 8192, 8192, partition=p2)
    assert m2.n_buffers == 2 and m1.n_buffers == 1
    assert m2.vmem_bytes() <= p2.budget_bytes
    assert (m2.bm * m2.bn, m2.bk) <= (m1.bm * m1.bn, m1.bk)
    assert m2.bm * m2.bk * m2.bn < m1.bm * m1.bk * m1.bn
    a1 = tiling.plan_attention(1 << 16, 1 << 16, 128, partition=p1)
    a2 = tiling.plan_attention(1 << 16, 1 << 16, 128, partition=p2)
    assert a2.block_q * a2.block_kv <= a1.block_q * a1.block_kv


def test_mempool_tile_rule_through_partition():
    """Acceptance: the paper's t = 256/384/544/800 via the partition path."""
    for mib, want in [(1, 256), (2, 384), (4, 544), (8, 800)]:
        target = get_target(f"mempool-2d-{mib}mib")
        part = tiling.mempool_partition(target.scratchpad_bytes)
        assert tiling.mempool_tile_size(target.scratchpad_bytes,
                                        partition=part) == want
        # the partition reproduces the paper's 3.25-tile working-set factor
        assert 2.0 * part.streamed_multiplier + 1.0 == pytest.approx(
            tiling.MEMPOOL_RESIDENT_TILES)


def test_target_partition_respects_scratchpad():
    for name in ("tpu-v5e", "mempool-3d-8mib"):
        t = get_target(name)
        part = t.partition(fraction=0.5)
        assert part.budget_bytes == int(t.kernel_scratchpad_bytes * 0.5)
        assert part.align == t.tile_align
    # MemPool tiles may fill the whole cluster SPM (no scoped limit)
    mp = get_target("mempool-3d-8mib")
    assert mp.kernel_scratchpad_bytes == mp.scratchpad_bytes


# ----------------------------------------------------------------- plan cache

def test_plan_cache_returns_same_object():
    planner.plan_cache_clear()
    p1 = planner.matmul_kernel_plan(2048, 2048, 2048)
    p2 = planner.matmul_kernel_plan(2048, 2048, 2048)
    assert p1 is p2
    info = planner.plan_cache_info()["matmul"]
    assert info.hits >= 1 and info.misses == 1


def test_plan_cache_keys_on_target_and_shape():
    planner.plan_cache_clear()
    base = planner.attention_plan(4096, 4096, 128)
    other_shape = planner.attention_plan(8192, 8192, 128)
    other_target = planner.attention_plan(4096, 4096, 128,
                                          target=get_target("tpu-v5p"))
    assert base is not other_shape
    assert base is not other_target
    assert planner.plan_cache_info()["attention"].misses == 3


def test_plan_cache_keys_on_dtype():
    planner.plan_cache_clear()
    bf16 = planner.matmul_kernel_plan(4096, 4096, 4096, in_bytes=2)
    f32 = planner.matmul_kernel_plan(4096, 4096, 4096, in_bytes=4)
    assert bf16 is not f32


def test_model_plans_threaded_once():
    """Model.kernel_plans goes through the planner cache: same shape cell ->
    same plan objects, no re-planning."""
    from repro.models import build_model
    from repro.models.config import ModelConfig
    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                      vocab_size=128)
    model = build_model(cfg)
    plans_a = model.kernel_plans(64, 64)
    plans_b = model.kernel_plans(64, 64)
    assert plans_a.attention is plans_b.attention
    assert plans_a.matmul is plans_b.matmul
    assert plans_a.target_name == get_target().name


def test_mempool_target_plans_shrink_with_capacity():
    """The same planning stack runs against MemPool targets: more SPM ->
    bigger matmul blocks (the paper's law through the unified interface)."""
    with use_target("mempool-2d-1mib"):
        small = tiling.plan_matmul(4096, 4096, 4096)
    with use_target("mempool-2d-8mib"):
        big = tiling.plan_matmul(4096, 4096, 4096)
    assert big.bm * big.bn >= small.bm * small.bn
    assert big.vmem_bytes() > small.vmem_bytes()


# ------------------------------------------------------------ tiered split

def test_stacked_partition_budgets():
    """TieredPartition stacks the same budget formula across two layers —
    the paper's die split: layer 0 keeps the base budget, layer 1 adds a
    fraction of the level's capacity on top."""
    part = CapacityPartition(capacity_bytes=1000, fraction=0.8, n_buffers=1)
    tiers = part.stacked(0.5)
    assert tiers.layer0 is part
    assert tiers.layer0.budget_bytes == 800
    assert tiers.layer1.budget_bytes == 400        # 1000 * 0.5 * 0.8
    assert tiers.budget_bytes == 1200              # the 3D capacity win
    assert tiers.tier_budgets() == (800, 400)


def test_stacked_partition_units_and_resident_charge():
    part = CapacityPartition(capacity_bytes=1000, fraction=1.0, n_buffers=1)
    tiers = part.stacked(1.0)
    # 100-byte units: 10 per layer; resident state charged to layer 0 only
    assert tiers.units_per_tier(100) == (10, 10)
    assert tiers.units_per_tier(100, resident_bytes=250) == (7, 10)


def test_stacked_partition_rejects_negative_layer1():
    part = CapacityPartition(capacity_bytes=1000, n_buffers=1)
    with pytest.raises(ValueError, match="layer1_fraction"):
        part.stacked(-0.1)
    empty = part.stacked(0.0)                      # a 2D flow: no layer 1
    assert empty.layer1.budget_bytes == 0
    assert empty.units_per_tier(100)[1] == 0
