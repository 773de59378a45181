"""bench/roofline.py against hand counts, and the peaks table."""

import json

import pytest

from bench import roofline
from bench.tests.smoke import BENCH

TARGET = json.loads((BENCH / "configs" / "dscoder33b-l6_1.3b.json").read_text())


def test_six_layer_target_bytes_match_the_chip_run():
    # 6 layers of 530,331,648 parameters (wq, wo 51,380,224 each; wk, wv
    # 7,340,032 each; wg, wu, wd 137,625,600 each; two norms of 7,168), plus
    # embedding and head (2 x 32,256 x 7,168) and the final norm, in bf16:
    # the 7,288,838,144 B PR 11 read on the chip
    dec = roofline.Decoder.of(TARGET)
    assert dec.layer_matrix_params + 2 * 7168 == 530_331_648
    assert dec.param_bytes() == 7_288_838_144


def test_twenty_layers_at_tp2_per_chip():
    dec = roofline.Decoder.of(dict(TARGET, num_hidden_layers=20))
    # (20 x 1,060,663,296 + 924,858,368) / 2
    assert dec.param_bytes(tp=2) == 11_069_062_144
    assert round(dec.param_bytes(tp=2) / 1e9, 2) == 11.07


def test_draft_bytes():
    assert roofline.Decoder.of(TARGET["draft"]).param_bytes() == 2_692_943_872


def test_call_flops_and_bytes_by_hand():
    dec = roofline.Decoder(vocab=10, d_model=4, n_layers=2, n_heads=2, n_kv_heads=1,
                           head_dim=2, d_ff=6, bytes_per=2)
    # layer matrices: 2*4*2*2 + 2*4*1*2 + 3*4*6 = 32 + 16 + 72 = 120
    assert dec.layer_matrix_params == 120
    flops, nbytes = dec.call(rows=3, tokens=2, kv_rows=5)
    n = 6
    matmul = 2 * 120 + 10 * 4  # layers and the head
    attn = 4 * 2 * 2 * 2 * (5 + 2)  # 4 * layers * heads * head_dim * keys
    assert flops == (2 * matmul + attn) * n
    weights = (2 * (120 + 8) + 40 + 4) * 2
    kv_row = 2 * 2 * 1 * 2 * 2  # K and V, layers, kv heads, head_dim, bytes
    assert nbytes == weights + n * 4 * 2 + 3 * 5 * kv_row + n * kv_row
    f2, b2 = dec.call(rows=3, tokens=2, kv_rows=5, tp=2)
    assert (f2, b2) == (flops / 2, nbytes / 2)


def test_least_time_names_its_bound():
    peak = roofline.peaks("TPU v5 lite")
    assert peak == {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
    t, bound = roofline.least_time(197e12, 1.0, peak)
    assert (t, bound) == (1.0, "compute")
    t, bound = roofline.least_time(1.0, 819e9, peak)
    assert (t, bound) == (1.0, "memory")


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no peaks for device kind 'TPU v9'"):
        roofline.peaks("TPU v9")


def test_peaks_table_names_its_source():
    table = json.loads(roofline.PEAKS.read_text())
    assert "cloud.google.com/tpu/docs/v5e" in table["source"]
