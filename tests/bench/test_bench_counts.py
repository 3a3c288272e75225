"""Operation and byte counts come from true lengths, whatever the kernel's
grid; peaks are keyed by device kind and an unknown kind is an error."""

from __future__ import annotations

import pytest

from bench import counts, peaks

QWEN = dict(hidden_size=2048, intermediate_size=11008,
            num_attention_heads=16, num_key_value_heads=2,
            num_hidden_layers=36, vocab_size=151936)
NEMO = dict(hidden_size=5120, intermediate_size=14336, head_dim=128,
            num_attention_heads=32, num_key_value_heads=8,
            num_hidden_layers=40, vocab_size=131072)


def test_peak_of_v5e_and_unknown_kind():
    p = peaks.peak_for("TPU v5 lite")
    assert (p.flops, p.hbm_bytes) == (197e12, 819e9)
    assert "TPU v5e" in p.source
    with pytest.raises(KeyError, match="no published peak"):
        peaks.peak_for("TPU v9 imaginary")


@pytest.mark.parametrize("shape,per_token_kv", [(QWEN, 36 * 2 * 2 * 128 * 2),
                                                (NEMO, 40 * 2 * 8 * 128 * 2)])
def test_page_walk_bytes_follow_the_true_length(shape, per_token_kv):
    f1, b1 = counts.page_walk(shape, 1000)
    f2, b2 = counts.page_walk(shape, 2000)
    # each further attended position costs its K and V in every layer
    assert b2 - b1 == pytest.approx(1000 * per_token_kv)
    assert f2 == pytest.approx(2 * f1)


def test_decode_steps_expand_spans_to_attended_lengths():
    # a slot at frontier 10 that produced 3 tokens attended 11, 12, 13
    assert list(counts.decode_steps([(10, 3), (5, 0), (0, 1)])) == \
        [11, 12, 13, 1]


def test_flash_counts_causal_pairs_at_the_true_length():
    f, b = counts.flash(QWEN, 2)
    # 3 query-key pairs per head, 4 flops per pair per head dim
    assert f == pytest.approx(36 * 4 * 16 * 128 * 3)
    assert b == pytest.approx(36 * (2 * 16 + 2 * 2) * 128 * 2 * 2)


def test_model_flops_count_every_weight_once():
    n = counts.layer_matmul_params(QWEN)
    assert n == 2048 * 128 * (16 + 4) + 16 * 128 * 2048 + 3 * 2048 * 11008
    per_tok = counts.prefill_flops(QWEN, 100) - counts.prefill_flops(QWEN, 99)
    # one more prompt token: its weights, plus its 100 causal pairs
    assert per_tok == pytest.approx(
        2 * n * 36 + 36 * 4 * 16 * 128 * 100)
    assert counts.decode_token_flops(QWEN, 1) > 2 * n * 36


def test_roofline_share_names_its_bound():
    share, bound = counts.roofline_share(1e12, 1e9, 1.0, 1e12, 1e12)
    assert (share, bound) == (100.0, "compute")
    share, bound = counts.roofline_share(1.0, 5e11, 1.0, 1e12, 1e12)
    assert (share, bound) == (50.0, "memory")
