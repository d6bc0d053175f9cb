"""A tiny cell for the benchmark's CPU tests: the qwen1.5-0.5b layout
(QKV bias) at toy widths, and the json.open mix cut down.

The head is untied here. At two layers of width 64 a tied head makes the
last input token's own logit dominate every step (its embedding is a
large part of the residual stream), so greedy decoding only repeats and
no comparison of logits could tell precisions apart. At 24 layers of
width 1024 the embedding is a small part of the stream."""
from __future__ import annotations

import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
for p in (str(ROOT / "src"), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)


def spec(grammar="json", kind="poisson", gap=0.1, mean=0.008) -> dict:
    cfg = json.loads((HERE / "configs" / "qwen1.5-0.5b.json").read_text())
    cfg["serve"].update(num_layers=2, d_model=64, num_heads=4,
                        num_kv_heads=2, head_dim=16, d_ff=128,
                        vocab_size=512, tie_embeddings=False,
                        name="tiny")
    cfg["check"] = {"max_logit_gap": gap, "mean_logit_gap": mean}
    mix = json.loads((HERE / "traffic" / "json.open.json").read_text())
    mix.update(grammar=grammar, slots=4, max_len=256, check_tokens=96,
               prompt_tokens={"median": 24, "sigma": 0.5, "min": 8,
                              "max": 64},
               new_tokens={"median": 12, "sigma": 0.5, "min": 4,
                           "max": 32}, table=64)
    if kind == "poisson":
        mix["arrivals"] = {"kind": "poisson", "rate_per_s": 6.0}
    else:
        mix["arrivals"] = {"kind": "backlog", "waiting": 8, "prepare": 64}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return {"cell": {"name": "tiny", "config": "tiny", "traffic": "tiny",
                     "chips": 1},
            "config": cfg, "mix": mix,
            "end_to_end": bench["end_to_end"],
            "per_layer": bench["per_layer"]}
