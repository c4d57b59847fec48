"""Counts from shapes against hand counts at smollm-360m's shapes."""

import json
from pathlib import Path

import pytest

from chipbench import counts
from chipbench.reference import llama

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
# SmolLM-360M per layer: q 960x960, k and v 960x320, o 960x960, gate, up
# and down 960x2560, two RMSNorm scales of 960.
PER_LAYER = 960 * 960 + 2 * 960 * 320 + 960 * 960 + 3 * 960 * 2560 + 2 * 960
EMBED = 49152 * 960


def config(layers=20):
    """The benchmark's SmolLM-360M file, at its 20 layers or whole (32)."""
    c = json.loads((CONFIGS / "smollm-360m-l20.json").read_text())
    return dict(c, num_hidden_layers=layers)


@pytest.mark.parametrize("layers", [32, 20])
def test_param_count_counts_the_tied_table_once(layers):
    c = config(layers)
    assert llama.layer_params(c) == PER_LAYER == 9_832_320
    assert counts.param_count(c) == layers * PER_LAYER + 960 + EMBED
    assert counts.param_count(config(32)) == 361_821_120


def test_untied_model_counts_its_head_but_not_its_lookup():
    c = dict(config(), tie_word_embeddings=False)
    assert counts.param_count(c) == 20 * PER_LAYER + 960 + 2 * EMBED
    assert llama.matmul_params(c) == 20 * PER_LAYER + 960 + EMBED


def test_train_flops_are_6nt_plus_causal_attention():
    c = config(32)
    traffic = {"workers": 4, "per_worker_batch": 1, "seq": 2048}
    T = 4 * 1 * 2048
    n = 32 * PER_LAYER + 960 + EMBED
    attention = 6 * 2048 * 960 * 32          # per token, the causal half
    assert counts.train_flops_per_token(c, 2048) == 6 * n + attention
    assert counts.train_flops_per_step(c, traffic) == T * (6 * n + attention)
    l20 = counts.train_flops_per_step(config(), traffic)
    assert l20 == pytest.approx(13.92e12, rel=1e-3)


@pytest.mark.parametrize("devices", [1, 4])
def test_kernel_bytes(devices):
    c = config(32)
    n = 361_821_120
    assert counts.gram_bytes(c, 4, devices) == 4 * n * 4 / devices
    assert counts.combine_bytes(c, 4, devices) == (4 + 1) * n * 4 / devices


def test_peaks_of_v5e_and_unknown_device_raises(tmp_path):
    p = counts.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        counts.peaks("TPU v9 imaginary")
    table = tmp_path / "peaks.json"
    table.write_text(json.dumps({"other": {}}))
    with pytest.raises(KeyError):
        counts.peaks("TPU v5 lite", table)
