"""The harness end to end on the CPU at a tiny size, and the entry point's
refusals.  A CPU run is a check of the harness's paths and of ``correct``;
its times mean nothing and are never reported."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import pytest

import bench_tiny
from bench import cells, harness

SEED = 2 ** 31 + 77


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return bench_tiny.make_root(tmp_path_factory.mktemp("tiny"))


def _run(root, seed=SEED):
    cell = cells.load_cell("tiny.chat", root)
    return harness.run(cell, seed, 2.0, False, jax.devices()[:1],
                       time.perf_counter())


def test_tiny_run_is_correct_and_reports_the_contract(tiny):
    res = _run(tiny)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] == 16 and res["failed"] == 0
    assert set(res["metrics"]) == {"ttft_p50_ms", "itl_p99_ms",
                                   "output_tok_per_s", "setup_s"}
    for m in res["metrics"].values():
        assert m["value"] >= 0 and m["unit"]
    assert res["device"]["count"] == 1
    chk = res["checks"]["logit_gap_max"]
    assert chk["value"] <= chk["limit"]


def test_a_token_altered_where_it_is_produced_fails(tiny, monkeypatch):
    import repro.serving.paged_engine as pe
    sample = pe.sample

    def altered(logits, sp, step):
        tok = sample(logits, sp, step)
        return (tok + 1) % 8192 if step == 3 else tok
    monkeypatch.setattr(pe, "sample", altered)
    res = _run(tiny)
    assert res["correct"] is False
    chk = res["checks"]["logit_gap_max"]
    assert chk["value"] > chk["limit"]


def _entry(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3-4b.chat",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_entry_point_refuses_the_cpu():
    p = _entry(bench_tiny.REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_entry_point_fails_with_only_the_benchmark_files(tmp_path):
    bm = json.loads((bench_tiny.REPO / "BENCHMARK.json").read_text())
    for path in bm["paths"]:
        shutil.copytree(bench_tiny.REPO / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(bench_tiny.REPO / "BENCHMARK.json", tmp_path)
    p = _entry(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_traced_window_records_host_spans(tiny, tmp_path):
    from bench import trace_reduce, traffic
    cell = cells.load_cell("tiny.chat", tiny)
    _, eng = harness.build_engine(cell, SEED)
    reqs = traffic.generate(cell.traffic, 8.0, 1.0, SEED,
                            cell.config["vocab_size"])
    harness.warm(eng, cell.workload["warm"], reqs)
    plan = {"first_step": 2, "steps": 4}
    win = harness.drive(eng, reqs, 1.0, harness.CompileStats(), trace=plan,
                        trace_dir=str(tmp_path / "trace"))
    assert win.trace_dir is not None
    traced = [s for s in win.steps if s.traced]
    assert traced == win.steps[2:6]
    assert any(s.decode_resident for s in traced)
    tr = trace_reduce.load(trace_reduce.find_xspace(win.trace_dir))
    names = {trace_reduce.span_family(s.name) for s in tr.spans}
    assert {"harness/step", "decode dispatch"} <= names
    steps = [s for s in tr.spans if s.name == "harness/step"]
    assert len(steps) == len(traced)
