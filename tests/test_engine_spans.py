"""Engine phase spans (``TraceRing.span``): one primitive feeding the trace
ring, the profiler's host plane and the engine's host-time counters under
one name, and the clock anchor that puts ring events on the profiler's
clock."""
import time
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import iso_cfg, tiny_dense
from repro.config import Config, ParallelConfig, ServingConfig
from repro.models import api
from repro.obs import TraceRing, chrome_trace, jaxprof, validate_chrome_trace
from repro.obs.registry import Counter
from repro.obs.trace import TraceEvent
from repro.serving import PagedEngine, Request
from repro.serving.paged_engine import HOST_COUNTERS
from repro.serving.requests import SamplingParams

PHASES = ("engine/step", "engine/admit", "engine/schedule", "engine/pages",
          "engine/inputs", "engine/device_wait", "engine/logits",
          "engine/sample", "engine/release")


# ---------------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ring_on", [True, False])
def test_span_self_time_into_counters(ring_on):
    ring = TraceRing(enabled=ring_on)
    outer, inner = Counter("outer"), Counter("inner")
    with ring.span("engine/a", outer) as a:
        time.sleep(0.002)
        with ring.span("engine/b", inner, n_pages=3) as b:
            time.sleep(0.003)
        with ring.span("engine/c"):                 # no counter of its own
            time.sleep(0.001)
    da, db = a.t1 - a.t0, b.t1 - b.t0
    assert a.t0 < b.t0 < b.t1 < a.t1
    assert inner.value == pytest.approx(db, abs=1e-9)
    # nested spans are not counted twice: the outer counter holds only the
    # time no child covered, so the counters never exceed the outer span
    assert outer.value < da - db
    assert outer.value + inner.value < da
    assert outer.value > 0.0015
    evs = ring.events()
    if not ring_on:
        assert evs == []
        return
    assert [e.kind for e in evs] == ["engine/b", "engine/c", "engine/a"]
    ev_b = evs[0]
    assert (ev_b.ts, ev_b.dur) == (b.t0, db)
    assert ev_b.payload == {"n_pages": 3}
    assert not ring._open


def test_span_annotates_under_the_same_name(monkeypatch):
    opened = []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(self.name)

        def __exit__(self, *exc):
            pass
    monkeypatch.setattr(jaxprof, "annotate", Recorder)
    monkeypatch.setattr(jaxprof, "_active", True)
    ring = TraceRing()
    with ring.span("engine/x", Counter("c")):
        with ring.span("decode/K=1/S=1"):
            pass
    assert opened == ["engine/x", "decode/K=1/S=1"]
    assert sorted(e.kind for e in ring.events()) == sorted(opened)


def test_span_without_profile_is_a_null_annotation():
    assert not jaxprof.profiling_active()
    assert jaxprof.annotate("engine/x") is jaxprof.annotate("engine/y")


def test_chrome_trace_anchor_moves_events_to_the_profiler_clock():
    evs = [TraceEvent(ts=9.0, kind="admit"),
           TraceEvent(ts=10.001, kind="engine/step", dur=0.002),
           TraceEvent(ts=10.004, kind="finish")]
    doc = chrome_trace(evs, anchor=(10.0, 500.0))
    assert validate_chrome_trace(doc) == []
    body = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    # the event before the anchor lies before the profile: left out
    assert [e["name"] for e in body] == ["engine/step", "finish"]
    assert body[0]["ts"] == pytest.approx(1500.0)
    assert body[0]["dur"] == pytest.approx(2000.0)
    assert body[1]["ts"] == pytest.approx(4500.0)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

def _engine(**sv):
    cfg = tiny_dense(vocab_size=64)
    params = api.init_params(jax.random.PRNGKey(0), cfg, tp=1,
                             dtype=jnp.float32)
    kw = dict(page_size=8, max_batch=3, max_len=160, prefill_token_budget=24)
    kw.update(sv)
    config = Config(model=cfg, parallel=ParallelConfig(data=1, model=1),
                    iso=iso_cfg(2, min_chunk_tokens=8, chunk_align=8),
                    serving=ServingConfig(**kw))
    return PagedEngine(config, params)


def _add(eng, lengths, new=5, seed=3):
    rng = np.random.default_rng(seed)
    for n in lengths:
        eng.add_request(Request(
            prompt=rng.integers(2, 64, n).astype(np.int32),
            sampling=SamplingParams(max_new_tokens=new, eos_id=-1)))


def _spans(eng):
    by = defaultdict(list)
    for e in eng.trace.events():
        if e.dur > 0 and "/" in e.kind:
            by[e.kind].append(e)
    return by


def test_host_counters_and_fenced_time_add_up_to_the_steps():
    # a tight pool: eviction releases pages inside engine/pages
    eng = _engine(num_pages=10)
    _add(eng, (40, 30, 21, 9, 17), new=6)
    eng.run_until_complete()
    assert eng.trace.dropped == 0
    assert eng.metrics["preemptions"] > 0
    by = _spans(eng)
    assert set(PHASES) <= set(by), sorted(by)
    m = eng.metrics
    steps = sum(e.dur for e in by["engine/step"])
    assert len(by["engine/step"]) == m["steps"]
    covered = sum(m[k] for k in HOST_COUNTERS) + m["prefill_s"] + \
        m["decode_s"]
    assert covered == pytest.approx(steps, rel=0.01)
    # the dispatch spans feed the dispatch counters, whose names stay
    pre = sum(e.dur for k, v in by.items() if k.startswith("prefill/")
              for e in v)
    dec = sum(e.dur for k, v in by.items() if k.startswith("decode/")
              for e in v)
    assert pre == pytest.approx(m["prefill_dispatch_s"], abs=1e-9)
    assert dec == pytest.approx(m["decode_dispatch_s"], abs=1e-9)
    assert all(e.payload["n_pages"] >= 0 for e in by["engine/release"])
    # every phase span lies inside a step
    st = [(e.ts, e.ts + e.dur) for e in by["engine/step"]]
    for kind in PHASES[1:]:
        for e in by[kind]:
            assert any(a <= e.ts and e.ts + e.dur <= b for a, b in st), kind


def test_first_grant_stamp_splits_ttft_and_survives_preemption():
    eng = _engine(num_pages=10)
    _add(eng, (40, 30, 21, 9, 17), new=6)
    states = dict(eng._by_rid)
    eng.run_until_complete()
    evs = eng.trace.events()
    first_commit = {}
    evicted = defaultdict(list)
    for e in evs:
        if e.kind == "grant_commit":
            first_commit.setdefault(e.rid, e.ts)
        elif e.kind == "evict":
            evicted[e.rid].append(e.ts)
    assert evicted
    for rid, st in states.items():
        assert st.t_submit <= st.t_first_grant <= first_commit[rid] \
            <= st.t_first
        # set once, at the first prefill call: later re-prefills after an
        # eviction do not move it
        for t in evicted[rid]:
            assert st.t_first_grant < t


def test_observability_off_still_counts_host_time():
    eng = _engine(observability=False)
    _add(eng, (20, 12), new=3)
    eng.run_until_complete()
    assert len(eng.trace) == 0
    assert all(eng.metrics[k] >= 0 for k in HOST_COUNTERS)
    assert eng.metrics["host_step_other_s"] > 0
    assert eng.metrics["host_sample_s"] > 0


def test_profiler_spans_match_the_ring_through_the_anchor(tmp_path):
    """A CPU profile of a tiny engine holds every engine span the ring
    holds, and the anchor puts each ring span within 0.2 ms of the
    profiler's span of the same name."""
    eng = _engine()
    _add(eng, (30, 12), new=4)
    eng.step()                                  # compile outside the profile
    jaxprof.start(str(tmp_path))
    try:
        for _ in range(4):
            eng.step()
    finally:
        jaxprof.stop()
    anchor = jaxprof.clock_anchor(str(tmp_path))
    assert anchor is not None
    prof = defaultdict(list)
    for name, start, dur in jaxprof.host_spans(str(tmp_path), prefix=""):
        prof[name].append((start, dur))
    doc = chrome_trace(eng.trace.events(), anchor=anchor)
    assert validate_chrome_trace(doc) == []
    ring = defaultdict(list)
    for e in doc["traceEvents"]:
        if e["ph"] == "X" and "/" in e["name"]:
            ring[e["name"]].append((e["ts"], e["dur"]))
    assert {"engine/step", "engine/schedule", "engine/inputs",
            "engine/device_wait", "engine/logits", "engine/sample"} <= \
        set(ring)
    assert any(k.startswith("prefill/") for k in ring)
    assert any(k.startswith("decode/") for k in ring)
    for name, spans in ring.items():
        assert len(prof[name]) == len(spans), name
        for (ts, dur), (p_ts, p_dur) in zip(sorted(spans),
                                            sorted(prof[name])):
            assert abs(ts - p_ts) < 200.0, (name, ts, p_ts)
            assert abs(ts + dur - p_ts - p_dur) < 200.0, name
