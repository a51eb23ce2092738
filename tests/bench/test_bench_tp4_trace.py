"""The trace reduction and the prefill collective reader on a trace recorded
on a 2x2 TPU v5e host: a traced run of qwen3-8b-tp4.docs (tensor parallel
4), cut to three consecutive engine steps, the first that hold a
prefill dispatch (device ``XLA Ops`` lines and the harness's and engine's
host spans kept)."""
import gzip
import math
import shutil
from pathlib import Path
from types import SimpleNamespace

import pytest

import bench_tiny  # noqa: F401
from bench import cells
from bench import trace_reduce as tr

DATA = Path(__file__).parent / "data" / "tp4_docs_trace.xplane.pb.gz"
# the grants (start, tokens, last) of the kept steps, from the same run
GRANTS = [[(2688, 2740, True)], [(0, 1739, True)], [(0, 2304, False)]]


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    path = tmp_path_factory.mktemp("tp4") / "t.xplane.pb"
    with gzip.open(DATA) as f, open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    t = tr.load(str(path))
    return t, tr.summarize(t)


def test_four_chips_and_the_harness_spans(summary):
    t, s = summary
    assert sorted(t.ops) == [0, 1, 2, 3]
    assert all(len(ops) > 1000 for ops in t.ops.values())
    assert {"harness/step", "prefill dispatch"} <= {
        tr.span_family(x.name) for x in t.spans}
    assert 0 < s.busy_s <= s.window_s
    assert s.busy_s + sum(s.idle_s.values()) == pytest.approx(s.window_s)


def test_prefill_collectives_are_found_and_bounded(summary):
    _, s = summary
    assert s.prefill_collective_s > 0
    assert 0 <= s.prefill_exposed_collective_s <= s.prefill_collective_s
    assert s.prefill_collective_s <= s.prefill_s <= s.busy_s


@pytest.mark.parametrize("metric", ["prefill_collective_ms_per_1k_tok",
                                    "exposed_collective_share"])
def test_collective_readers_give_a_finite_number(summary, metric):
    _, s = summary
    run = SimpleNamespace(trace=s, traced_steps=[
        SimpleNamespace(grants=g) for g in GRANTS])
    v = cells.reader(metric)(run)
    assert v is not None and math.isfinite(v) and v > 0
    if metric == "exposed_collective_share":
        assert v <= 100
