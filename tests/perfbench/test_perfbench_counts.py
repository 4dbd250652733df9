"""The benchmark's FLOP and byte counts against hand-worked values, for both
configurations, and the peaks table."""
import json
import os

import pytest

import _paths  # noqa: F401
import counts
import peaks


def _config(name):
    with open(os.path.join(_paths.PERFBENCH, "configs", name + ".json")) as f:
        return json.load(f)


# worked by hand from the published shapes:
#   large : 50257*1280 + 1024*1280 + 36*(12*1280^2 + 13*1280) + 2*1280
#   medium: 50257*1024 + 1024*1024 + 24*(12*1024^2 + 13*1024) + 2*1024
HAND = {
    "gpt2-large": dict(
        n_params=64_328_960 + 1_310_720 + 36 * 19_677_440 + 2_560,       # 774,030,080
        train_flops_per_token=6 * 774_030_080 + 6 * 36 * 1280 * 1024,     # 4,927,296,000
        kv_bytes_per_position=2 * 2 * 36 * 1280,                          # 184,320
        decode_weight_bytes=2 * (36 * 19_677_440 + 2_560 + 64_328_960),
        flash_flops=36 * 3.5 * 2 * 16 * 1024 * 1024 * 1280,
        lm_head_flops=6 * 16 * 1024 * 1280 * 50257),
    "gpt2-medium": dict(
        n_params=51_463_168 + 1_048_576 + 24 * 12_596_224 + 2_048,        # 354,823,168
        train_flops_per_token=6 * 354_823_168 + 6 * 24 * 1024 * 1024,
        kv_bytes_per_position=2 * 2 * 24 * 1024,                          # 98,304
        decode_weight_bytes=2 * (24 * 12_596_224 + 2_048 + 51_463_168),
        flash_flops=24 * 3.5 * 2 * 16 * 1024 * 1024 * 1024,
        lm_head_flops=6 * 16 * 1024 * 1024 * 50257),
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_counts_match_hand_worked_values(name):
    cfg, hand = _config(name), HAND[name]
    assert counts.n_params(cfg) == hand["n_params"]
    assert counts.train_flops_per_token(cfg, 1024) == hand["train_flops_per_token"]
    assert counts.kv_bytes_per_position(cfg) == hand["kv_bytes_per_position"]
    assert counts.decode_weight_bytes(cfg) == hand["decode_weight_bytes"]
    assert counts.flash_attn_flops_per_step(cfg, 16, 1024) == hand["flash_flops"]
    assert counts.lm_head_loss_flops_per_step(cfg, 16, 1024) == hand["lm_head_flops"]


def test_published_sizes():
    assert counts.n_params(_config("gpt2-large")) == 774_030_080
    assert counts.n_params(_config("gpt2-medium")) == 354_823_168


@pytest.mark.parametrize("name", sorted(HAND))
def test_serve_flops_span_is_the_sum_over_contexts(name):
    cfg = _config(name)
    by_hand = sum(counts.serve_flops_per_token(cfg, c) for c in range(5, 41))
    assert counts.serve_flops_span(cfg, 5, 40) == pytest.approx(by_hand, rel=1e-12)
    assert counts.serve_flops_span(cfg, 7, 6) == 0.0


def test_decode_step_bytes_adds_weights_once_and_kv_per_context():
    cfg = _config("gpt2-large")
    got = counts.decode_step_bytes(cfg, [100, 200, 300])
    assert got == counts.decode_weight_bytes(cfg) + 184_320 * 600


@pytest.mark.parametrize("name", sorted(HAND))
def test_configuration_is_at_published_widths_with_nothing_reduced(name):
    cfg = _config(name)
    assert cfg["reduced"] == []
    assert cfg["n_embd"] % cfg["n_head"] == 0 and cfg["n_embd"] // cfg["n_head"] == 64
    assert cfg["vocab_size"] == 50257 and cfg["n_positions"] == 1024
    # what the capacity note claims, from the counts
    cap = cfg["serve"]
    pool = cap["num_slots"] * cap["max_context"] * counts.kv_bytes_per_position(cfg)
    assert 4.5e9 < pool < 6.5e9          # 29% to 41% of the chip's 16 GB


def test_peaks_table_is_keyed_by_device_kind_and_refuses_unknown_kinds():
    p = peaks.peaks_for("TPU v5 lite")
    assert p.bf16_flops_per_s == 197e12 and p.hbm_bytes_per_s == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks_for("cpu")
