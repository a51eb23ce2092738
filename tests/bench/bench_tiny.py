"""A tiny benchmark tree for CPU tests of the harness.

``make_root(tmp)`` copies the real readers and references and adds a tiny
Qwen3-shaped configuration, a short chat mix and a cell over them, with a
peaks entry for the CPU so the harness can run end to end without a chip.
The CPU peaks exist only in this test tree.

The tiny configuration is untied: with tied embeddings and two layers the
residual stream is mostly the current token's embedding, so the model
echoes its input by a wide margin and no precision can flip a token."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

BENCH = REPO / "bench"

TINY_CONFIG = {
    "source": "tiny stand-in for tests", "reference": "qwen3_dense",
    "reduced": [], "assumed": {}, "chips": 1, "tp": 1,
    "model_type": "qwen3", "hidden_act": "silu", "attention_bias": False,
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 8192, "rope_theta": 1000000, "rope_scaling": None,
    "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "use_sliding_window": False, "torch_dtype": "bfloat16",
}

TINY_MIX = {
    "arrivals": {"kind": "poisson"},
    "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.6, "min": 8,
               "max": 120},
    "output": {"dist": "lognormal", "median": 16, "sigma": 0.5, "min": 4,
               "max": 40},
}

TINY_CELL = {
    "config": "tiny", "traffic": "tinychat", "chips": 1, "rate_per_s": 8.0,
    "iso": {"enabled": True, "num_chunks": 2, "min_chunk_tokens": 16,
            "chunk_align": 16},
    "serving": {"page_size": 16, "max_batch": 4, "max_len": 160,
                "num_pages": 40, "prefill_token_budget": 48},
    "warm": {"decode": [[1, 1], [1, 4]],
             "prefill": [[t, r, f] for t in (16, 32, 64) for r in (1, 2)
                         for f in (0, 1)]},
    "trace": {"first_step": 4, "steps": 6},
    # readings on the CPU over nine seeds, every request served to its
    # end: program 0.0012-0.0039, float8 control 0.0088-0.0296
    "correct": {"max_requests": 12, "min_tokens": 100,
                "logit_gap_max": 0.006},
}


def make_root(tmp: Path, config: dict = None, cell: dict = None,
              mix: dict = None) -> Path:
    root = Path(tmp) / "bench"
    for sub in ("reference", "metrics"):
        shutil.copytree(BENCH / sub, root / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for sub in ("configs", "traffic", "workloads"):
        (root / sub).mkdir(parents=True, exist_ok=True)
    (root / "configs" / "tiny.json").write_text(
        json.dumps(config or TINY_CONFIG))
    (root / "traffic" / "tinychat.json").write_text(
        json.dumps(mix or TINY_MIX))
    (root / "workloads" / "tiny.chat.json").write_text(
        json.dumps(cell or TINY_CELL))
    peaks = json.loads((BENCH / "peaks.json").read_text())
    peaks["devices"]["cpu"] = {"bf16_flops_per_s": 1e12,
                               "hbm_bytes_per_s": 1e11}
    (root / "peaks.json").write_text(json.dumps(peaks))
    bm = json.loads((REPO / "BENCHMARK.json").read_text())
    for m in bm["end_to_end"]:
        m.pop("workloads", None)
    for m in bm["per_layer"]:
        m["workloads"] = ["tiny.chat"]
    (Path(tmp) / "BENCHMARK.json").write_text(json.dumps(bm))
    return root
