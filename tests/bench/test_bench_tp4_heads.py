"""The harness at tensor parallel 4 with Qwen3-8B's head layout per chip, on
four virtual CPU devices: 32 query and 8 KV heads over four chips (8 and 2
each) at a tiny head size, an untied head whose vocabulary is sharded.
Prompts are long enough that grants run in two ISO chunks and the longer
ones go over the prefill budget and resume through the paged prefill
kernel.  Checks ``correct`` against the float32 reference, ISO on against
off, and the engine counter ``prefill_iso_tokens``.  A subprocess, because
the device count is fixed when XLA starts."""
import subprocess
import sys

import bench_tiny

_CHILD = r"""
import os, sys, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import dataclasses, tempfile
from pathlib import Path
import jax, jax.numpy as jnp, numpy as np
import bench_tiny
from bench import cells, harness
from repro.core.chunking import round_to_bucket
from repro.serving import PagedEngine

config = dict(bench_tiny.TINY_CONFIG, num_attention_heads=32,
              num_key_value_heads=8, chips=4, tp=4)
mix = dict(bench_tiny.TINY_MIX,
           prompt={"dist": "lognormal", "median": 56, "sigma": 0.4,
                   "min": 24, "max": 120},
           output={"dist": "uniform", "min": 4, "max": 12})
# limit as in bench_tiny.TINY_CELL; readings at this layout over six seeds:
# program 0.0007-0.0031, float8 control 0.0167-0.0259
# calls of 32 positions or fewer run in one chunk, longer ones in two
cell = dict(bench_tiny.TINY_CELL, chips=4, rate_per_s=6.0,
            iso={"enabled": True, "num_chunks": 2, "min_chunk_tokens": 32,
                 "chunk_align": 16},
            correct={"max_requests": 12, "min_tokens": 50,
                     "logit_gap_max": 0.006})
root = bench_tiny.make_root(Path(tempfile.mkdtemp()), config=config,
                            cell=cell, mix=mix)
c = cells.load_cell("tiny.chat", root)
built = []
build = harness.build_engine
harness.build_engine = lambda *a: built.append(build(*a)) or built[-1]
res = harness.run(c, 2 ** 33 + 5, 1.5, False, jax.devices()[:4],
                  time.perf_counter())
assert res["correct"] is True, res["checks"]
weights, eng = built[0]
assert eng.tp == 4 and eng.kv.arrays["k"][0].sharding.shard_shape(
    eng.kv.arrays["k"][0].shape)[2] == 2          # two KV heads per chip
assert weights["embed"]["head"].sharding.spec[0] == "model"
m = eng.metrics
assert m["resumed_grants"] > 0, "no grant resumed through the paged kernel"
# every prefill call's padded length, from the trace ring: a call of T
# positions runs in two ISO chunks when T >= 2 * min_chunk_tokens
iso, total = 0, 0
for ev in eng.trace.events():
    if ev.kind != "prefill_call":
        continue
    p = ev.payload
    T = (p["tokens"] + p["pad"]) // round_to_bucket(p["rows"],
                                                    eng._row_buckets)
    total += p["tokens"]
    if T >= 2 * c.workload["iso"]["min_chunk_tokens"]:
        iso += p["tokens"]
assert total == m["prefill_tokens"]
assert 0 < iso < total, (iso, total)
assert m["prefill_iso_tokens"] == iso, (m["prefill_iso_tokens"], iso)
print("sharded ok", res["checks"]["logit_gap_max"], iso, total, flush=True)

# ISO on against off: one fresh 64-token call, then a resumed 64-token
# call over its pages, each in two chunks (on) and in one (off)
off_cfg = dataclasses.replace(eng.config, iso=dataclasses.replace(
    eng.config.iso, enabled=False))
off = PagedEngine(off_cfg, weights, mesh=eng.mesh)
rng = np.random.default_rng(3)
toks = jnp.asarray(rng.integers(0, 8192, (1, 128)), jnp.int32)
bt = np.full((1, eng.max_blocks), -1, np.int32)
bt[0, :8] = np.arange(8)
bt = jnp.asarray(bt)
kv = off.kv.arrays
logits = {}
for name, e in (("on", eng), ("off", off)):
    with e._mesh_ctx():
        fresh, kv1 = e._get_prefill_batched(64, 1, True)(
            e.params, toks[:, :64], kv, bt, jnp.zeros(1, jnp.int32),
            jnp.full(1, 64, jnp.int32))
        resumed, _ = e._get_prefill_batched(64, 1, False)(
            e.params, toks[:, 64:], kv1, bt, jnp.full(1, 64, jnp.int32),
            jnp.full(1, 64, jnp.int32))
    logits[name] = np.stack([np.asarray(fresh, np.float32),
                             np.asarray(resumed, np.float32)])
scale = float(np.abs(logits["off"]).max())
diff = float(np.abs(logits["on"] - logits["off"]).max())
# bf16 activations: splitting the call moves roundings of the residual
# stream, not the mathematics; 2% of the largest logit is about three bf16
# ulps at that size (one ulp measured)
assert diff <= 0.02 * scale, (diff, scale)
print("iso on/off", diff, scale, flush=True)
"""


def test_tp4_qwen3_8b_head_layout_is_correct_and_counts_iso_tokens():
    p = subprocess.run(
        [sys.executable, "-c", _CHILD, str(bench_tiny.REPO),
         str(bench_tiny.REPO / "tests" / "bench")],
        capture_output=True, text=True, timeout=900,
        env={**__import__("os").environ,
             "PYTHONPATH": str(bench_tiny.REPO / "src")})
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-5000:]
    assert "sharded ok" in p.stdout and "iso on/off" in p.stdout
