"""Everything is found by name, in files of its own (bench/cells.py)."""
import json

import pytest

import bench_tiny
from bench import cells, traffic


def test_real_cells_load_by_name():
    bm = json.loads((bench_tiny.REPO / "BENCHMARK.json").read_text())
    for w in bm["workloads"]:
        cell = cells.load_cell(w["name"])
        assert cell.workload["config"] == w["config"]
        assert cell.workload["traffic"] == w["traffic"]
        assert cell.chips == w["chips"]
        cfg = cells.model_config(cell)
        assert cfg.num_layers == cell.config["num_hidden_layers"]
        assert cfg.tie_embeddings == cell.config["tie_word_embeddings"]
        for m in cells.cell_metrics(cell, "per_layer"):
            assert callable(cells.reader(m["name"]))
    for c in bm["configs"]:
        assert (bench_tiny.REPO / c["file"]).is_file()


def test_qwen3_4b_follows_the_published_config():
    cell = cells.load_cell("qwen3-4b.chat")
    cfg = cells.model_config(cell)
    assert (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.resolved_head_dim, cfg.d_ff, cfg.vocab_size) == \
        (2560, 32, 8, 128, 9728, 151936)
    assert cfg.tie_embeddings and cfg.qk_norm and cfg.rope_theta == 1e6


def test_new_cell_and_metric_are_new_files_only(tmp_path):
    root = bench_tiny.make_root(tmp_path)
    cell2 = dict(bench_tiny.TINY_CELL, rate_per_s=3.0)
    (root / "workloads" / "tiny.other.json").write_text(json.dumps(cell2))
    (root / "metrics" / "dummy_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    bm_path = tmp_path / "BENCHMARK.json"
    bm = json.loads(bm_path.read_text())
    bm["per_layer"].append({"name": "dummy_metric", "unit": "ms",
                            "better": "lower", "source": "host_clock",
                            "layer": "test", "moves": "ttft_p50_ms",
                            "workloads": ["tiny.other"]})
    bm_path.write_text(json.dumps(bm))
    cell = cells.load_cell("tiny.other", root)
    assert cell.workload["rate_per_s"] == 3.0
    names = [m["name"] for m in cells.cell_metrics(cell, "per_layer")]
    assert names == ["dummy_metric"]
    assert cells.reader("dummy_metric", root)(None) == 42.0
    # a per-layer metric with no workloads key follows its end-to-end one
    bm["per_layer"][-1].pop("workloads")
    bm_path.write_text(json.dumps(bm))
    assert "dummy_metric" in [m["name"] for m in
                              cells.cell_metrics(cell, "per_layer")]


def test_unknown_names_and_devices_are_errors(tmp_path):
    root = bench_tiny.make_root(tmp_path)
    with pytest.raises(FileNotFoundError):
        cells.load_cell("no-such-cell", root)
    with pytest.raises(FileNotFoundError):
        cells.reader("no_such_metric", root)
    with pytest.raises(KeyError, match="not in"):
        cells.peaks("TPU v99 imaginary", root)
    assert cells.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_chips_must_match_the_config(tmp_path):
    root = bench_tiny.make_root(tmp_path)
    bad = dict(bench_tiny.TINY_CELL, chips=4)
    (root / "workloads" / "tiny.bad.json").write_text(json.dumps(bad))
    with pytest.raises(ValueError, match="chips"):
        cells.load_cell("tiny.bad", root)


@pytest.mark.parametrize("path", sorted(
    (bench_tiny.BENCH / "workloads").glob("*.json")), ids=lambda p: p.stem)
def test_every_cell_file_loads_and_warms_its_own_keys(path):
    cell = cells.load_cell(path.stem)
    w = cell.workload
    assert w["rate_per_s"] > 0 and w["chips"] == cell.tp
    cfg = cells.model_config(cell)
    config = cells.engine_config(cell, cfg)
    assert config.serving.max_len >= traffic.longest_request(cell.traffic)
    # every warm-up key is a (length bucket, row bucket, fresh) triple the
    # engine can form: lengths on the grant ladder, rows on the row ladder
    from repro.core.chunking import grant_buckets
    lengths = grant_buckets(config.serving.max_len,
                            config.serving.min_grant_bucket)
    rows = grant_buckets(config.serving.max_batch, min_bucket=1)
    for T, r, fresh in w["warm"]["prefill"]:
        assert T in lengths and r in rows and fresh in (0, 1)
    assert [1, 1] in w["warm"]["decode"]
