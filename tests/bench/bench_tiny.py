"""A small cell for the CPU tests: a root of its own holding the bench's
readers and reference, a two-layer decoder and a short mix."""

import json
import shutil
from pathlib import Path

from bench.spec import ROOT


def make_root(root: Path, *, dtype="bfloat16", norm="rmsnorm",
              closed=False, head_start=False, limit=0.05) -> Path:
    shutil.copytree(ROOT / "bench", root / "bench")
    kv = 2 if norm == "rmsnorm" else 4
    config = {"bench": {
        "reference": "dense_decoder", "dtype": dtype, "vocab_tokens": 500,
        "norm_eps": 1e-6,
        "model": {"name": "tiny", "family": "dense", "n_layers": 2,
                  "d_model": 64, "n_heads": 4, "n_kv_heads": kv,
                  "d_head": 16, "d_ff": 128, "vocab": 512, "norm": norm,
                  "act": "silu", "glu": True, "tie_embeddings": True,
                  "rope": "neox", "rope_theta": 10000.0},
        "serving": {"slots": 4, "max_len": 128, "page_tokens": 16,
                    "chunk_tokens": 32, "token_budget": 64},
        "check": {"max_logit_gap": limit, "min_tokens": 20}}}
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(config))
    mix = {"arrivals": ({"kind": "closed", "outstanding_per_slot": 2}
                        if closed else {"kind": "poisson",
                                        "rate_per_s": 20.0}),
           "prompt": {"dist": "lognormal", "median": 16, "sigma": 0.8,
                      "min": 4, "max": 48},
           "output": {"dist": "uniform", "min": 4, "max": 24},
           "ramp_s": 0.3, "block": 16, "check": {"requests": 4}}
    if head_start:
        mix["head_start"] = {"per_slot": 1}
        mix["ramp_s"] = 0
    (root / "bench" / "traffic" / "tiny-mix.json").write_text(
        json.dumps(mix))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "x", "reduced": [],
                         "file": "bench/configs/tiny.json", "why": "x"}]
    bench["workloads"] = [{"name": "tiny.mix", "config": "tiny",
                           "traffic": "tiny-mix", "chips": 1, "why": "x"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.mix"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
