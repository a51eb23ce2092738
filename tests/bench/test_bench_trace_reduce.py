"""Trace reduction (bench/trace_reduce.py) on synthetic intervals."""
import pytest

import bench_tiny  # noqa: F401
from bench import trace_reduce as tr
from bench.trace_reduce import Op, Span, Trace


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.total([(0, 2), (1, 3)]) == 3
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert tr.intersect([(0, 10)], [(2, 3), (9, 12)]) == [(2, 3), (9, 10)]
    assert tr.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert tr.clip([(0, 4), (6, 9)], 2, 7) == [(2, 4), (6, 7)]


def _trace():
    # window [0, 100): step spans [0, 40) and [50, 100); the host was in a
    # decode dispatch over [0, 5) and a prefill dispatch over [50, 52)
    spans = [Span("harness/step", 0, 40), Span("decode/K=1/S=4", 0, 5),
             Span("harness/add_request", 42, 45),
             Span("harness/step", 50, 100), Span("prefill/T=512x1", 50, 52)]
    ops = [Op("%while.9 = (s32[]) while(%t)", 5, 30),
           Op("flash_decode", 5, 15), Op("fusion.1", 15, 30),
           Op("fusion.2", 55, 70), Op("all-reduce.3", 70, 80),
           Op("fusion.4", 75, 78), Op("flash_prefill_paged", 80, 90)]
    return Trace(ops={0: ops, 1: ops}, spans=spans)


def test_summary_busy_idle_and_kernels():
    s = tr.summarize(_trace())
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(25e-9 + 35e-9)
    assert s.kernel_s("flash_decode") == pytest.approx(10e-9)
    assert s.op_s["fusion"] == pytest.approx((15 + 15 + 3) * 1e-9)
    # idle: [0,5) in the decode dispatch; [30,40) in the step (engine host
    # code); [40,42) and [45,50) waiting; [42,45) in add_request;
    # [50,52) in the prefill dispatch; [52,55) and [90,100) in the step
    assert s.idle_s["decode dispatch"] == pytest.approx(5e-9)
    assert s.idle_s["prefill dispatch"] == pytest.approx(2e-9)
    assert s.idle_s["harness/step"] == pytest.approx(23e-9)
    assert s.idle_s["harness/add_request"] == pytest.approx(3e-9)
    assert s.idle_s["harness/wait"] == pytest.approx(7e-9)
    assert s.busy_s + sum(s.idle_s.values()) == pytest.approx(s.window_s)


def test_exposed_collective_in_prefill():
    s = tr.summarize(_trace())
    # prefill call extent [50, 100): device busy 35, all-reduce 10, of
    # which [75, 78) overlaps compute
    assert s.prefill_s == pytest.approx(35e-9)
    assert s.prefill_collective_s == pytest.approx(10e-9)
    assert s.prefill_exposed_collective_s == pytest.approx(7e-9)


def test_breakdown_lists_top_ops_and_idle_spans():
    b = tr.summarize(_trace()).breakdown(top=2)
    assert [k for k, _ in b["device_ops"]] == ["fusion", "flash_decode"]
    assert b["idle_gaps"][0][0] == "harness/step"
    assert len(b["idle_gaps"]) == 2


def test_families():
    assert tr.op_family("fusion.12.3") == "fusion"
    assert tr.op_family("%flash_prefill_paged.10 = (f32[1,8,4,1024,128]) "
                        "custom-call(s32[1,288] %b), name=x") == \
        "flash_prefill_paged"
    assert tr.op_family("%while.3 = (s32[]) while((s32[]) %t)") == "while"
    assert tr.span_family("prefill/T=512x2") == "prefill dispatch"
    assert tr.span_family("harness/results") == "harness/results"


def test_no_steps_is_an_error():
    with pytest.raises(ValueError):
        tr.summarize(Trace(ops={0: [Op("x", 0, 1)]}, spans=[]))


def test_recorded_chip_trace(tmp_path):
    """A trace recorded on one TPU v5e in a traced run of qwen3-4b.chat
    (3.7 s, six engine calls): the reduction finds the harness's and the
    engine's spans, both paged kernels, and busy + idle = window."""
    import gzip
    import shutil
    from pathlib import Path
    src = Path(__file__).parent / "data" / "chat_trace.xplane.pb.gz"
    path = tmp_path / "t.xplane.pb"
    with gzip.open(src) as f, open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    t = tr.load(str(path))
    assert list(t.ops) == [0] and len(t.ops[0]) > 20000
    assert {"harness/step", "harness/results", "prefill dispatch",
            "decode dispatch"} <= {tr.span_family(s.name) for s in t.spans}
    s = tr.summarize(t)
    assert s.window_s == pytest.approx(3.7373100320000003)
    assert 0 < s.busy_s <= s.window_s
    assert s.busy_s + sum(s.idle_s.values()) == pytest.approx(s.window_s)
    b = s.breakdown()
    assert b["device_ops"][0][0] == "flash_prefill_paged"
    assert s.kernel_s("flash_decode") > 0
    assert "while" not in s.op_s
    # one chip: nothing to expose
    assert s.prefill_s > 0 and s.prefill_collective_s == 0
