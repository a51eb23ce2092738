"""The engine's phase spans and request stamps as the benchmark reads them
(bench/engine_spans.py and the readers built on it)."""
import gzip
import shutil
from dataclasses import fields, replace
from pathlib import Path

import pytest

import bench_tiny
from bench import cells, engine_spans, harness, stats, traffic
from bench import trace_reduce as tr
from bench.trace_reduce import Op, Span, Trace

SEED = 2 ** 31 + 913
PHASES = ("engine/step", "engine/schedule", "engine/pages", "engine/inputs",
          "engine/device_wait", "engine/logits", "engine/sample")


def _trace(engine: bool) -> Trace:
    # window [0, 100): two harness steps; inside the first, the engine's
    # step with its phases around a decode call whose device work runs
    # [6, 30); inside the second, a prefill call running [55, 90)
    spans = [Span("harness/step", 0, 40), Span("decode/K=1/S=4", 5, 6),
             Span("harness/add_request", 42, 45),
             Span("harness/step", 50, 100), Span("prefill/T=512x1", 52, 54)]
    if engine:
        spans += [Span("engine/step", 1, 39), Span("engine/pages", 2, 4),
                  Span("engine/device_wait", 6, 30),
                  Span("engine/logits", 30, 33),
                  Span("engine/sample", 33, 36),
                  Span("engine/release", 34, 35),
                  Span("engine/step", 51, 99),
                  Span("engine/schedule", 51, 52),
                  Span("engine/device_wait", 54, 90)]
    ops = [Op("%while.9 = (s32[]) while(%t)", 6, 30),
           Op("flash_decode", 6, 15), Op("fusion.1", 15, 30),
           Op("fusion.2", 55, 70), Op("all-reduce.3", 70, 80),
           Op("fusion.4", 75, 78), Op("flash_prefill_paged", 80, 90)]
    return Trace(ops={0: ops, 1: ops},
                 spans=sorted(spans, key=lambda s: s.start))


def test_engine_spans_name_idle_and_change_nothing_else():
    plain, named = tr.summarize(_trace(False)), tr.summarize(_trace(True))
    for f in fields(tr.Summary):
        if f.name != "idle_s":
            assert getattr(named, f.name) == getattr(plain, f.name), f.name
    assert sum(named.idle_s.values()) == \
        pytest.approx(sum(plain.idle_s.values()))
    # idle goes to the innermost span open in it
    want = {"harness/step": 4e-9, "engine/step": 14e-9,
            "engine/pages": 2e-9, "decode dispatch": 1e-9,
            "engine/logits": 3e-9, "engine/sample": 2e-9,
            "engine/release": 1e-9, "engine/schedule": 1e-9,
            "prefill dispatch": 2e-9, "engine/device_wait": 1e-9,
            "harness/add_request": 3e-9, "harness/wait": 7e-9}
    assert named.idle_s.keys() == want.keys()
    for k, v in want.items():
        assert named.idle_s[k] == pytest.approx(v), k
    idle = sum(want.values())
    assert engine_spans.named_idle_share(named) == \
        pytest.approx((idle - 4e-9 - 14e-9 - 3e-9 - 7e-9) / idle)


def test_recorded_chip_trace_reads_the_same(tmp_path):
    """The fixture predates the engine spans: loading it for them changes
    no field of the summary."""
    src = Path(__file__).parent / "data" / "chat_trace.xplane.pb.gz"
    path = tmp_path / "t.xplane.pb"
    with gzip.open(src) as f, open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    a = tr.summarize(tr.load(str(path)))
    b = tr.summarize(engine_spans.load(str(path)))
    for f in fields(tr.Summary):
        assert getattr(a, f.name) == getattr(b, f.name), f.name


@pytest.fixture(scope="module")
def traced_window(tmp_path_factory):
    root = bench_tiny.make_root(tmp_path_factory.mktemp("tiny"))
    cell = cells.load_cell("tiny.chat", root)
    _, eng = harness.build_engine(cell, SEED)
    reqs = traffic.generate(cell.traffic, 8.0, 3.0, SEED,
                            cell.config["vocab_size"])
    harness.warm(eng, cell.workload["warm"], reqs)
    tdir = tmp_path_factory.mktemp("trace")
    win = harness.drive(eng, reqs, 3.0, harness.CompileStats(),
                        trace={"first_step": 30, "steps": 12},
                        trace_dir=str(tdir))
    data = harness.RunData(
        cell=cell, shape=None, peaks={}, chips=1, window=win)
    return root, data


def test_traced_profile_holds_the_engine_spans(traced_window):
    _, data = traced_window
    t = engine_spans.load(tr.find_xspace(data.window.trace_dir))
    names = {s.name for s in t.spans}
    assert set(PHASES) <= names, sorted(names)
    assert engine_spans.ANCHOR not in names
    steps = [s for s in t.spans if s.name == "harness/step"]
    for s in t.spans:
        if s.name.startswith("engine/"):
            assert any(h.start <= s.start and s.end <= h.end
                       for h in steps), s


def test_stamps_split_each_ttft(traced_window):
    """Queue wait and prefill-to-first-token add up to no more than the
    harness's TTFT; the rest is the handover's lateness and the part of
    the step that yielded the token after the engine stamped it."""
    _, data = traced_window
    w = data.window
    step_of = {s.t1: s for s in w.steps}
    n = 0
    for i, st in w.states.items():
        rec = w.records[i]
        if rec.first is None:
            continue
        qw, pf = st.t_first_grant - st.t_submit, st.t_first - st.t_first_grant
        assert qw >= 0 and pf > 0
        ttft = rec.first - rec.due
        assert qw + pf <= ttft
        step = step_of[rec.first]
        assert ttft - qw - pf <= (rec.submitted - rec.due) + \
            (step.t1 - step.t0)
        n += 1
    assert n >= 8


@pytest.mark.parametrize("metric,part", [
    ("queue_wait_ms_p50", lambda sub, g, f: g - sub),
    ("prefill_to_first_token_ms_p50", lambda sub, g, f: f - g)])
def test_stamp_readers(traced_window, metric, part):
    root, data = traced_window
    assert metric in {m["name"] for m in
                      cells.cell_metrics(data.cell, "per_layer")}
    stamps = engine_spans.due_stamps(data)
    assert stamps
    v = cells.reader(metric, root)(data)
    assert v == pytest.approx(
        1e3 * stats.percentile([part(*s) for s in stamps], 50))
    assert v >= 0


def test_stamps_end_where_the_profiler_stopped(traced_window):
    """Only requests that got their first token by the end of the last
    traced step count: the stall that stopping the profiler causes holds
    up the rest."""
    _, data = traced_window
    w = data.window
    firsts = sorted(w.records[i].first for i in w.states
                    if w.t0 <= w.records[i].due < w.t1
                    and w.records[i].first is not None)
    cut = firsts[len(firsts) // 2]          # a step's end, as every first
    early = replace(w, steps=[replace(s, traced=s.t1 <= cut)
                              for s in w.steps])
    run = harness.RunData(cell=data.cell, shape=None, peaks={}, chips=1,
                          window=early)
    got = engine_spans.due_stamps(run)
    assert len(got) == sum(1 for f in firsts if f <= cut) < len(firsts)
    assert {g for _, g, _ in got} == {
        w.states[i].t_first_grant for i in w.states
        if w.t0 <= w.records[i].due < w.t1
        and w.records[i].first is not None and w.records[i].first <= cut}


def test_stamp_readers_find_nothing_without_stamps(traced_window):
    """A program that does not stamp ``t_first_grant`` gives no reading."""
    root, data = traced_window

    class Unstamped:
        def __init__(self, st):
            self.t_submit, self.t_first = st.t_submit, st.t_first
    w = data.window
    bare = harness.RunData(
        cell=data.cell, shape=None, peaks={}, chips=1,
        window=harness.Window(
            t0=w.t0, t1=w.t1, deadline=w.deadline, records=w.records,
            states={i: Unstamped(st) for i, st in w.states.items()},
            steps=w.steps, compiles=0, cache_hits=0, preemptions=0,
            counters=w.counters))
    for metric in ("queue_wait_ms_p50", "prefill_to_first_token_ms_p50"):
        assert cells.reader(metric, root)(bare) is None
