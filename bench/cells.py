"""Find everything a run needs by name, in files of its own.

    bench/configs/<config>.json      a model configuration: every width, the
                                     published source, what was reduced or
                                     assumed, chips and tensor parallelism,
                                     and the name of its plain reference
    bench/reference/<reference>.py   that reference
    bench/traffic/<mix>.json         a traffic mix (bench/traffic.py)
    bench/workloads/<cell>.json      a cell: config, mix, rate, engine
                                     settings, warm-up keys, trace window and
                                     correctness limits
    bench/metrics/<metric>.py        a per-layer metric reader: ``read(run)``
                                     returns a float, or None when the run
                                     holds nothing to read
    bench/peaks.json                 peaks per device kind
    BENCHMARK.json                   which metrics each cell reports

A new configuration, mix, cell or metric is a new file; nothing here names
one.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parent


def _read(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    with open(path) as f:
        return json.load(f)


@dataclass(frozen=True)
class Cell:
    name: str
    workload: dict           # bench/workloads/<name>.json
    config: dict             # bench/configs/<config>.json
    traffic: dict            # bench/traffic/<mix>.json
    root: Path

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    @property
    def tp(self) -> int:
        return int(self.config["tp"])


def load_cell(name: str, root: Path = ROOT) -> Cell:
    w = _read(root / "workloads" / f"{name}.json")
    cfg = _read(root / "configs" / f"{w['config']}.json")
    mix = _read(root / "traffic" / f"{w['traffic']}.json")
    if int(cfg["chips"]) != int(w["chips"]):
        raise ValueError(f"cell {name}: {w['chips']} chips, config "
                         f"{w['config']} needs {cfg['chips']}")
    return Cell(name, w, cfg, mix, root)


def benchmark(root: Path = ROOT) -> dict:
    return _read(root.parent / "BENCHMARK.json")


def cell_metrics(cell: Cell, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports: those
    that list it, or list no cells and move a metric the cell reports."""
    b = benchmark(cell.root)
    e2e = [m for m in b["end_to_end"]
           if cell.name in m.get("workloads", [cell.name])]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}

    def reported(m):
        if "workloads" in m:
            return cell.name in m["workloads"]
        return m["moves"] in names
    return [m for m in b["per_layer"] if reported(m)]


def reader(metric: str, root: Path = ROOT) -> Callable:
    """``read`` of bench/metrics/<metric>.py."""
    path = root / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for per-layer metric {metric}: "
                                f"{path}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reference(cell: Cell):
    """The configuration's plain reference module."""
    name = cell.config["reference"]
    path = cell.root / "reference" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    table = _read(root / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"{root / 'peaks.json'}; add its published peaks")
    return table[device_kind]


# ---------------------------------------------------------------------------
# program configuration from the files
# ---------------------------------------------------------------------------

def model_config(cell: Cell):
    """The program's ModelConfig, built from the configuration file alone."""
    from repro.config import ModelConfig
    c = cell.config
    if c.get("model_type") != "qwen3" or c.get("hidden_act") != "silu" or \
            c.get("attention_bias") or c.get("use_sliding_window") or \
            c.get("rope_scaling"):
        raise ValueError(f"{cell.workload['config']}: only dense Qwen3 "
                         f"configurations map onto the program so far")
    return ModelConfig(
        name=cell.workload["config"], family="dense",
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        qk_norm=True, rope_theta=float(c["rope_theta"]),
        rms_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]), source=c["source"])


def engine_config(cell: Cell, cfg=None):
    """The program's Config for this cell's engine."""
    from repro.config import (Config, ISOConfig, ParallelConfig,
                              RuntimeConfig, ServingConfig)
    w = cell.workload
    return Config(model=cfg or model_config(cell),
                  parallel=ParallelConfig(data=1, model=cell.tp),
                  iso=ISOConfig(**w["iso"]),
                  runtime=RuntimeConfig(mode="serve"),
                  serving=ServingConfig(**w["serving"]))
