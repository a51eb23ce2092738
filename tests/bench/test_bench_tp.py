"""The harness at tensor parallel 4 on four virtual CPU devices, tiny size:
the sharded path is correct, and with the exchange between chips left out
``correct`` comes out false.  A subprocess, because the device count is
fixed when XLA starts."""
import subprocess
import sys

import bench_tiny

_CHILD = r"""
import os, sys, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import tempfile
from pathlib import Path
import jax
import bench_tiny
from bench import cells, harness

config = dict(bench_tiny.TINY_CONFIG, num_attention_heads=8,
              num_key_value_heads=4, chips=4, tp=4,
              tie_word_embeddings=False)
cell = dict(bench_tiny.TINY_CELL, chips=4)
root = bench_tiny.make_root(Path(tempfile.mkdtemp()), config=config,
                            cell=cell)
c = cells.load_cell("tiny.chat", root)
res = harness.run(c, 2 ** 33 + 1, 1.5, False, jax.devices()[:4],
                  time.perf_counter())
assert res["correct"] is True, res["checks"]
assert res["device"]["count"] == 4
print("sharded ok", res["checks"]["logit_gap_max"], flush=True)

import repro.core.overlap as ov
ov._reduce = lambda x, ctx: x          # the exchange between chips left out
res = harness.run(c, 2 ** 33 + 1, 1.5, False, jax.devices()[:4],
                  time.perf_counter())
assert res["correct"] is False, res["checks"]
print("no exchange fails", res["checks"]["logit_gap_max"], flush=True)
"""


def test_tp4_sharded_run_is_correct_and_needs_its_all_reduces():
    p = subprocess.run(
        [sys.executable, "-c", _CHILD, str(bench_tiny.REPO),
         str(bench_tiny.REPO / "tests" / "bench")],
        capture_output=True, text=True, timeout=900,
        env={**__import__("os").environ,
             "PYTHONPATH": str(bench_tiny.REPO / "src")})
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-5000:]
    assert "sharded ok" in p.stdout and "no exchange fails" in p.stdout
