"""Percentiles, spreads, FLOPs and bytes against hand-worked cases."""

import json
import os

import pytest

from benchmark.harness import costs, spec, stats

CONFIGS = os.path.join(spec.BENCH_DIR, "configs")


def _model(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_percentile_nearest_rank():
    vals = list(range(1, 21))                  # 1..20
    assert stats.percentile(vals, 95) == (19, 20)   # ceil(.95*20) = 19th
    assert stats.percentile(vals, 50) == (10, 20)
    assert stats.percentile(vals, 100) == (20, 20)
    assert stats.percentile([7.0], 95) == (7.0, 1)
    assert stats.percentile([3, 1, 2], 34) == (2, 3)   # ceil(1.02) = 2nd
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_iqr_share_is_pythons_quartiles():
    # statistics.quantiles([1..6], n=4) = [1.75, 3.5, 5.25]
    assert stats.iqr_share([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)
    assert stats.iqr_share([10, 10, 10, 10]) == 0


def test_mistral_parameters_and_kv_bytes():
    m = _model("mistral-7b-v0.3-serve")
    # a layer: q 4096*4096 + k,v 2*4096*1024 + o 4096*4096 = 41,943,040;
    # mlp 3*4096*14336 = 176,160,768; 12 layers + head 4096*32768
    assert costs.matmul_params(m) == 12 * (41943040 + 176160768) + 134217728
    assert costs.total_params(m) == costs.matmul_params(m) + 134217728 \
        + 25 * 4096
    assert round(costs.total_params(m) / 1e9, 2) == 2.89
    # K and V, 12 layers, 8 heads of 128, bf16 = 48 KiB a token
    assert costs.kv_bytes_per_token(m) == 49152
    assert costs.paged_attention_bytes(m, 1000) == 49152000
    assert round(costs.weight_bytes(m) / 2**30, 1) == 5.4


def test_internlm2_train_flops():
    m = _model("internlm2-1.8b-train")
    # a layer: q,o 2*2048*2048 + k,v 2*2048*1024 = 12,582,912;
    # mlp 3*2048*8192 = 50,331,648; 24 layers + head 2048*92544
    mm = 24 * (12582912 + 50331648) + 189530112
    assert costs.matmul_params(m) == mm == 1699479552
    assert round(costs.total_params(m) / 1e9, 2) == 1.89
    # causal attention forward for one 4096 sequence:
    # 4 * 4096^2 * 16 * 128 * 24 / 2 = 1,649,267,441,664
    assert costs.attention_flops_fwd(m, 4096) == 1649267441664
    per_token = 6 * mm + 3 * 1649267441664 / 4096
    assert costs.train_flops_per_token(m, 4096) == per_token
    assert round(per_token / 1e9, 1) == 11.4
    f = costs.flash_flops(m, 4096, n_seqs=2)
    assert f == {"fwd": 2 * 1649267441664, "bwd": 5 * 1649267441664}
    # the embedding table is not in it: 6*N with N = all parameters
    # would read 12.5 % higher
    assert 6 * costs.total_params(m) / (6 * mm) == pytest.approx(1.1116,
                                                                 abs=1e-3)


def test_peaks_known_and_unknown():
    p = costs.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        costs.peaks("cpu")
