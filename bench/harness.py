"""One run of one cell: set-up, the measured window, the check, the result.

Set-up builds the weights (bench/weights.py), the program's ``PagedEngine``
with the cell's settings, and runs every compiled program the cell's
traffic uses once (the keys listed in the cell's ``warm``).  The window then
drives the engine only through ``add_request`` and ``step``, from one
thread: each request is handed over at the first step boundary after it
is due, whatever the engine is doing (an open loop), and every token is
stamped with the return of the ``step`` that yielded it.  After the window
closes the loop keeps stepping, without new arrivals, until every request
due in the window has its first token and enough requests have finished for
the correctness sample, or a minute has passed.

With a trace, the profiler runs over a fixed range of engine steps set in
the cell (``trace``: ``first_step`` and ``steps``, counted from the window's
first step), so every traced run covers the same part of the fixed
schedule, and the host spans
``harness/step``, ``harness/add_request`` and ``harness/results`` go into it
beside the engine's own ``prefill/...`` and ``decode/...`` spans.
"""
from __future__ import annotations

import contextlib
import gc
import shutil
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import cells as cells_lib
from bench import correct as correct_lib
from bench import flops as flops_lib
from bench import stats as stats_lib
from bench import trace_reduce
from bench import traffic as traffic_lib
from bench import weights as weights_lib

DRAIN_S = 60.0
WARM_THREADS = 8


def log(tag: str, **kv) -> None:
    print(f"[{tag}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          file=sys.stderr, flush=True)


class CompileStats:
    """XLA compiles (with their seconds) and persistent-cache hits and
    misses, from JAX's monitoring events."""

    def __init__(self):
        from jax import monitoring
        self.compiles, self.seconds = 0, 0.0
        self.cache_hits, self.cache_misses = 0, 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> Tuple[int, int]:
        return self.compiles, self.cache_hits


class GcClock:
    """The longest pause of Python's garbage collector while it is open."""

    def __init__(self):
        self.n, self.max_s, self._t = 0, 0.0, None
        gc.callbacks.append(self)

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.n += 1
            self.max_s = max(self.max_s, time.perf_counter() - self._t)

    def close(self):
        gc.callbacks.remove(self)


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def build_engine(cell, seed: int):
    """Weights from the seed, then the program's engine on them."""
    from repro.serving import PagedEngine
    cfg = cells_lib.model_config(cell)
    config = cells_lib.engine_config(cell, cfg)
    mesh = None
    if cell.tp > 1:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh(config.parallel)
    weights = weights_lib.make_weights(cfg, cell.tp, seed, mesh=mesh)
    jax.block_until_ready(weights)
    return weights, PagedEngine(config, weights, mesh=mesh)


def warm(eng, keys: dict, reqs: List[traffic_lib.TrafficRequest]) -> int:
    """Run each compiled program the cell's traffic uses once, on inputs
    that touch nothing but the pool's scratch page.  Returns the count."""
    B, MB = eng.max_batch, eng.max_blocks
    n = 0

    def run(fn, *args):
        with eng._mesh_ctx():
            out = fn(eng.params, *args)
        jax.block_until_ready(out)
        return out

    for K, S in keys.get("decode", []):
        for get in ([eng._get_decode] + ([eng._get_fallback_decode]
                    if eng._decode_schedule == "batch_split" else [])):
            _, eng.kv.arrays, eng.states = run(
                get(K, S), jnp.zeros((B, K), jnp.int32),
                jnp.asarray(np.full((B, MB), -1, np.int32)),
                jnp.asarray(np.zeros(B, np.int32)), eng.kv.arrays,
                eng.states, jnp.asarray(np.zeros(B, bool)))
            n += 1
    for T, rows, fresh in keys.get("prefill", []):
        _, eng.kv.arrays = run(
            eng._get_prefill_batched(T, rows, all_fresh=bool(fresh)),
            jnp.asarray(np.zeros((rows, T), np.int32)), eng.kv.arrays,
            jnp.asarray(np.full((rows, MB), -1, np.int32)),
            jnp.asarray(np.zeros(rows, np.int32)),
            jnp.asarray(np.zeros(rows, np.int32)))
        n += 1
    # the engine frees a request's pages, when it finishes or is preempted,
    # with eager ops whose programs depend on the page count (nine
    # compiles, about a second each on a TPU): warm every count a request
    # of this traffic can hold, compiling them side by side
    pos = eng.kv.arrays["pos"]
    scratch = eng.kv.scratch_page

    def release(k):
        jax.block_until_ready(pos.at[jnp.asarray([scratch] * k,
                                                 jnp.int32)].set(-1))
    counts = traffic_lib.release_page_counts(reqs, eng.ps)
    with ThreadPoolExecutor(WARM_THREADS) as pool:
        list(pool.map(release, counts))
    return n + len(counts)


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

@dataclass
class StepRecord:
    t0: float
    t1: float
    prefill_s: float                  # engine's fenced time in this step
    decode_s: float
    grants: List[Tuple[int, int, bool]] = field(default_factory=list)
    decode_resident: List[int] = field(default_factory=list)
    traced: bool = False


@dataclass
class Window:
    t0: float
    t1: float
    deadline: float
    records: List[stats_lib.RequestRecord]
    states: Dict[int, object]         # index -> engine RequestState
    steps: List[StepRecord]
    compiles: int
    cache_hits: int
    preemptions: int
    counters: Dict[str, float]        # engine counters' rise in the window
    trace_dir: Optional[str] = None


def _span(name: str, on: bool):
    return jax.profiler.TraceAnnotation(name) if on else \
        contextlib.nullcontext()


COUNTERS = ("prefill_s", "decode_s", "prefill_tokens", "prefill_pad_tokens",
            "decode_calls", "prefill_calls", "steps", "preemptions",
            "decode_tokens")


def drive(eng, reqs: List[traffic_lib.TrafficRequest], seconds: float,
          compiles: CompileStats, trace: Optional[dict] = None,
          trace_dir: Optional[str] = None, finished_tokens: int = 0
          ) -> Window:
    """Run the window, then drain: keep stepping, without new arrivals,
    until every request due in the window has its first token and the
    finished requests hold ``finished_tokens`` served tokens (what the
    correctness sample needs), or until ``DRAIN_S`` past the close."""
    from repro.obs import jaxprof
    from repro.serving import Request
    from repro.serving.requests import SamplingParams

    records = [stats_lib.RequestRecord(due=0.0) for _ in reqs]
    states: Dict[int, object] = {}
    live: Dict[int, Tuple[int, int]] = {}     # index -> (prefilled, ngen)
    by_rid: Dict[int, int] = {}
    steps: List[StepRecord] = []
    m = eng.metrics
    c0 = {k: float(m[k]) for k in COUNTERS}
    nxt, n = 0, len(reqs)
    done_tokens = 0
    tracing = traced = False
    t0 = time.perf_counter()
    t1 = t0 + seconds
    deadline = t1 + DRAIN_S
    for r, rec in zip(reqs, records):
        rec.due = t0 + r.due_s
    comp0 = compiles.snapshot()
    comp1 = None
    while True:
        now = time.perf_counter()
        if comp1 is None and now >= t1:
            comp1 = compiles.snapshot()
            c1 = {k: float(m[k]) for k in COUNTERS}
        if trace and not traced and len(steps) >= trace["first_step"]:
            jaxprof.start(trace_dir)
            tracing = traced = True
        if tracing and len(steps) >= trace["first_step"] + trace["steps"]:
            jaxprof.stop()
            tracing = False
        while nxt < n and records[nxt].due <= now:
            r, rec = reqs[nxt], records[nxt]
            with _span("harness/add_request", tracing):
                rec.submitted = time.perf_counter()
                try:
                    rid = eng.add_request(Request(
                        prompt=r.prompt, sampling=SamplingParams(
                            max_new_tokens=r.max_new, eos_id=-1)))
                except ValueError as e:          # refused: counts as failed
                    rec.error = str(e)
                else:
                    by_rid[rid] = nxt
                    states[nxt] = eng._by_rid[rid]
                    live[nxt] = (0, 0)
            nxt += 1
        if now >= t1 and done_tokens >= finished_tokens and all(
                rec.first is not None or rec.error for rec in records):
            break
        if now >= deadline:
            break
        if not live:
            if nxt < n:
                time.sleep(max(0.0, records[nxt].due - time.perf_counter()))
            else:
                time.sleep(0.001)
            continue
        before = {i: (states[i].prefilled, len(states[i].generated))
                  for i in live}
        p0, d0 = float(m["prefill_s"]), float(m["decode_s"])
        s0 = time.perf_counter()
        with _span("harness/step", tracing):
            events = eng.step()
        s1 = time.perf_counter()
        with _span("harness/results", tracing):
            rec_step = StepRecord(s0, s1, float(m["prefill_s"]) - p0,
                                  float(m["decode_s"]) - d0, traced=tracing)
            for rid, tok in events:
                i = by_rid[rid]
                records[i].token_times.append(s1)
                records[i].tokens.append(int(tok))
            for i, (pre0, g0) in before.items():
                st = states[i]
                P = st.prompt_len
                if st.prefilled < pre0:           # preempted: recompute
                    live[i] = (st.prefilled, len(st.generated))
                    continue
                if st.prefilled > pre0:
                    rec_step.grants.append((pre0, st.prefilled - pre0,
                                            st.prefilled >= P))
                g1 = len(st.generated)
                n_dec = g1 - g0 - (1 if pre0 < P <= st.prefilled else 0)
                rec_step.decode_resident.extend(
                    P + j - 1 for j in range(g1 - n_dec, g1))
                if st.done:
                    del live[i]
                    done_tokens += g1
                else:
                    live[i] = (st.prefilled, g1)
            steps.append(rec_step)
    if tracing:
        jaxprof.stop()
    if comp1 is None:
        comp1 = compiles.snapshot()
        c1 = {k: float(m[k]) for k in COUNTERS}
    return Window(t0=t0, t1=t1, deadline=deadline, records=records,
                  states=states, steps=steps,
                  compiles=comp1[0] - comp0[0],
                  cache_hits=comp1[1] - comp0[1],
                  preemptions=int(m["preemptions"] - c0["preemptions"]),
                  counters={k: c1[k] - c0[k] for k in COUNTERS},
                  trace_dir=trace_dir if traced else None)


# ---------------------------------------------------------------------------
# what per-layer readers see
# ---------------------------------------------------------------------------

@dataclass
class RunData:
    """Everything a per-layer metric reader may read (bench/metrics/)."""
    cell: object
    shape: flops_lib.Shape
    peaks: dict
    chips: int
    window: Window
    trace: Optional[trace_reduce.Summary] = None

    @property
    def window_steps(self) -> List[StepRecord]:
        w = self.window
        return [s for s in self.window.steps if w.t0 <= s.t0 < w.t1]

    @property
    def traced_steps(self) -> List[StepRecord]:
        return [s for s in self.window.steps if s.traced]

    def traced_model_flops(self) -> int:
        f = 0
        for s in self.traced_steps:
            f += sum(flops_lib.prefill_model_flops(self.shape, a, b, last)
                     for a, b, last in s.grants)
            f += sum(flops_lib.decode_model_flops(self.shape, r)
                     for r in s.decode_resident)
        return f

    def least_seconds(self, work: Tuple[int, int]) -> float:
        t, _ = flops_lib.least_seconds(
            work[0], work[1], self.peaks["bf16_flops_per_s"],
            self.peaks["hbm_bytes_per_s"], self.chips)
        return t


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run(cell, seed: int, seconds: float, trace: bool, devices,
        t_start: float, keep_trace: Optional[str] = None) -> dict:
    """Set up, measure, check; returns the result line's object."""
    compiles = CompileStats()
    dev = devices[0]
    peaks = cells_lib.peaks(dev.device_kind, cell.root)
    w = cell.workload
    reqs = traffic_lib.generate(cell.traffic, float(w["rate_per_s"]),
                                seconds, seed, cell.config["vocab_size"])
    weights, eng = build_engine(cell, seed)
    n_warm = warm(eng, w["warm"], reqs)
    c_setup = compiles.snapshot()
    setup_s = time.perf_counter() - t_start
    log("setup", setup_s=setup_s, programs_warmed=n_warm,
        compiles=c_setup[0], compile_s=compiles.seconds,
        cache_hits=compiles.cache_hits, cache_misses=compiles.cache_misses,
        num_pages=eng.alloc.num_pages, max_batch=eng.max_batch,
        pool_bytes=eng.kv.total_bytes(),
        weight_bytes=weights_lib.tree_bytes(weights))

    tmp = None
    if trace:
        tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        gc_clock = GcClock()
        win = drive(eng, reqs, seconds, compiles,
                    trace=w["trace"] if trace else None, trace_dir=tmp,
                    finished_tokens=w["correct"]["min_tokens"])
        gc_clock.close()
        mem = memory_peak(devices)
        late = stats_lib.lateness(win.records)
        slow = max(win.steps, key=lambda s: s.t1 - s.t0, default=None)
        log("window", seconds=seconds, requests=len(reqs), steps=len(
            win.steps), longest_step_s=slow and slow.t1 - slow.t0,
            longest_step_at_s=slow and slow.t0 - win.t0,
            gc_runs=gc_clock.n, gc_longest_s=gc_clock.max_s,
            compiles_in_window=win.compiles,
            cache_loads_in_window=win.cache_hits,
            preemptions=win.preemptions, memory_peak_bytes=mem)
        log("generator", **{f"late_{k}": v for k, v in late.items()})
        warmed = {tuple(k) for k in w["warm"]["prefill"]}
        used = {(k[0], k[1], int(k[2])) for k in eng._prefill_fns}
        dec = set(eng._decode_fns) | set(eng._decode_fallback_fns)
        log("programs", prefill_used=sorted(used),
            prefill_not_warmed=sorted(used - warmed),
            decode_used=sorted(dec), decode_not_warmed=sorted(
                dec - {tuple(k) for k in w["warm"]["decode"]}))
        done_outputs = {i: list(r.tokens) for i, r in enumerate(win.records)
                        if i in win.states and win.states[i].done}
        # free the program's state before the reference runs
        del eng
        gc.collect()
        summary = None
        if win.trace_dir:
            summary = trace_reduce.summarize(trace_reduce.load(
                trace_reduce.find_xspace(win.trace_dir)))
            if keep_trace:
                shutil.copytree(win.trace_dir, keep_trace,
                                dirs_exist_ok=True)
        checks = correct_lib.check(cell, weights, reqs, done_outputs, seed)
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)

    failed = sum(1 for r in win.records if r.first is None)
    data = RunData(cell=cell, shape=flops_lib.Shape.from_config(cell.config),
                   peaks=peaks, chips=cell.chips, window=win, trace=summary)
    metrics: Dict[str, dict] = {}
    if trace:
        for m in cells_lib.cell_metrics(cell, "per_layer"):
            v = cells_lib.reader(m["name"], cell.root)(data)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        e2e = stats_lib.end_to_end(win.records, win.t0, win.t1,
                                   win.deadline)
        e2e["setup_s"] = setup_s
        for m in cells_lib.cell_metrics(cell, "end_to_end"):
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    checks["requests_without_first_token"] = {"value": failed, "limit": 0}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": all(v["value"] <= v["limit"]
                             for v in checks.values()),
              "attempted": len(reqs), "failed": failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    for k, v in checks.items():
        log("check", name=k, value=v["value"], limit=v["limit"])
    result["checks"] = checks
    return result
