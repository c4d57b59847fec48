"""A cell at a size a CPU test can hold: the program's reduced smollm
variant (``--debug``: 2 layers, d_model 256, 4 heads, 2 KV heads, vocab
512, fp32 compute) under a benchmark traffic mix at seq 64, held to the
benchmark cell's own limits."""

import json
from pathlib import Path

from chipbench import spec

HERE = Path(__file__).resolve().parents[1]

CONFIG = {
    "name": "tiny", "source": "test", "reference": "llama",
    "program_arch": "smollm-360m", "chips": 1, "sharded_agg": False,
    "hidden_size": 256, "intermediate_size": 512,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 2, "vocab_size": 512,
    "max_position_embeddings": 64, "rms_norm_eps": 1e-06,
    "rope_theta": 10000.0, "tie_word_embeddings": True,
    "hidden_act": "silu", "attention_bias": False,
    "param_dtype": "float32", "compute_dtype": "float32",
}


class TinyCell(spec.Cell):
    def train_argv(self) -> list[str]:
        return super().train_argv() + ["--debug"]


def cell(workload: str = "smollm-l20-flag-w4", chips: int = 1,
         seq: int = 64) -> spec.Cell:
    bench = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    entry = next(w for w in bench["workloads"] if w["name"] == workload)
    traffic = json.loads(
        (HERE / "traffic" / f"{entry['traffic']}.json").read_text())
    traffic["seq"] = seq
    limits = json.loads((HERE / "limits" / f"{workload}.json").read_text())
    config = dict(CONFIG, chips=chips, sharded_agg=chips > 1)
    return TinyCell(f"tiny-{workload}", chips, config, traffic, limits, [])
