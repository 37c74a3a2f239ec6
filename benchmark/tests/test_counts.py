"""The roofline counts, from shapes, against numbers worked out by hand."""
import pytest

from benchmark import common, counts

BASE = common.load_json(common.HERE, "configs", "bert-base-wwm-ext-causal.json")
PEAK = common.peaks_for("TPU v5 lite")


def test_peaks_table():
    assert PEAK == {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9}
    with pytest.raises(SystemExit):
        common.peaks_for("TPU v9 imaginary")


def test_train_step_flops_is_six_times_params_times_tokens_plus_attention():
    per_layer = 4 * 768 * 768 + 2 * 768 * 3072          # 7 077 888 multiply-adds
    tokens = 64 * 128
    dense = 6 * 12 * per_layer * tokens
    attn = 3 * 12 * 4 * 128 * 768 * tokens
    head = 3 * 64 * 2 * (768 * 768 + 768 * 6)
    got = counts.train_step_flops({**BASE, "num_labels": 6}, 64, 128)
    assert got == pytest.approx(dense + attn + head, rel=1e-12)
    assert got == pytest.approx(4.32e12, rel=0.01)


def test_param_count_is_bert_base():
    # 12 x 7 087 872 in the layers, 16 622 592 in the embeddings and their
    # LayerNorm, 613 256 in the head (transform, LayerNorm, output bias)
    assert counts.param_count(BASE) == 85_054_464 + 16_622_592 + 613_256
    assert counts.kv_bytes_per_token(BASE) == 36864


def test_decode_step_is_bandwidth_bound_and_counts_each_byte_once():
    live = 128 * 300
    r = counts.decode_step_min_seconds(BASE, rows=128, live_tokens=live, peak=PEAK)
    want = counts.param_count(BASE) * 2 + (live + 128) * 36864
    assert r["bytes"] == want and r["bound"] == "bytes"
    assert r["seconds"] == pytest.approx(want / 819e9)
    # 0.2 GB of weights + 1.4 GB of K/V at 819 GB/s: about 2 ms
    assert 1.5e-3 < r["seconds"] < 2.5e-3
    empty = counts.decode_step_min_seconds(BASE, rows=1, live_tokens=0, peak=PEAK)
    assert empty["seconds"] == pytest.approx(
        (counts.param_count(BASE) * 2 + 36864) / 819e9)
