"""The plain float32 reference (bench/reference/qwen3_dense.py) against the
program's PagedEngine logits, at a tiny width, with float32 weights so the
two differ only by summation order."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
from bench import cells, correct, weights


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    root = bench_tiny.make_root(tmp_path_factory.mktemp("ref"))
    cell = cells.load_cell("tiny.chat", root)
    cfg = cells.model_config(cell)
    w = weights.make_weights(cfg, 1, seed=5)
    w32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w)
    return cell, cfg, w32


def test_weights_have_the_programs_layout(setup):
    cell, cfg, _ = setup
    w = weights.make_weights(cfg, 1, seed=2 ** 40 + 3)
    shapes = weights.abstract_params(cfg, 1)
    got = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), w)
    want = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), shapes)
    assert got == want
    again = weights.make_weights(cfg, 1, seed=2 ** 40 + 3)
    assert all(bool(jnp.all(a == b)) for a, b in zip(
        jax.tree_util.tree_leaves(w), jax.tree_util.tree_leaves(again)))


def test_reference_matches_paged_engine_logits(setup, monkeypatch):
    from repro.serving import PagedEngine, Request
    from repro.serving.requests import SamplingParams
    import repro.serving.paged_engine as pe
    cell, cfg, w32 = setup
    eng = PagedEngine(cells.engine_config(cell, cfg), w32)
    seen = {}
    sample = pe.sample

    def keep(logits, sp, step):
        seen[(sp.seed, step)] = np.asarray(logits, np.float32).copy()
        return sample(logits, sp, step)
    monkeypatch.setattr(pe, "sample", keep)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 512, n).astype(np.int32)
               for n in (100, 33, 70, 9)]
    rids = [eng.add_request(Request(prompt=p, sampling=SamplingParams(
        max_new_tokens=5, eos_id=-1, seed=i))) for i, p in enumerate(prompts)]
    out = eng.run_until_complete()
    assert eng.metrics["resumed_grants"] > 0
    ref = cells.reference(cell)
    worst = 0.0
    for i, (rid, p) in enumerate(zip(rids, prompts)):
        toks = np.concatenate([p, np.asarray(out[rid][:-1], np.int32)])
        rows = np.arange(len(p) - 1, len(toks))
        r = ref.logits(w32, cell.config, toks, rows)
        for j in range(len(rows)):
            got = seen[(i, j)][:cfg.vocab_size]
            worst = max(worst, float(np.max(np.abs(got - r[j]))
                                     / np.max(np.abs(r[j]))))
        # greedy: every served token is the reference's best
        gaps = correct.served_gaps(ref, w32, cell.config, p, out[rid])
        assert float(gaps.max()) <= 1e-4
    assert worst < 1e-4


def test_fp8_control_moves_the_logits(setup):
    cell, _, w32 = setup
    ref = cells.reference(cell)
    toks = np.random.default_rng(1).integers(0, 512, 64).astype(np.int32)
    rows = np.arange(64)
    a = ref.logits(w32, cell.config, toks, rows, "fp32")
    b = ref.logits(w32, cell.config, toks, rows, "fp8")
    rel = np.max(np.abs(a - b)) / np.max(np.abs(a))
    assert 1e-3 < rel < 0.5
