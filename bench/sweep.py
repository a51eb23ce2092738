#!/usr/bin/env python3
"""Find a cell's knee: the highest of a few fixed rates its engine sustains.

    python bench/sweep.py --workload <cell> --seconds <s> --rates 0.5 1 2 ...

One process, one set of weights; for each rate a fresh engine, the cell's
warm-up, and one window of the cell's traffic at that rate (seed fixed by
``--seed``).  A rate is sustained when every request due in the window got
its first token by the window's close plus ``--drain`` seconds and the
median time to first token of the last third of the requests is at most
twice that of the first third (no growing backlog).  One JSON line per rate.
The benchmark's own runs never run this; its result sets ``rate_per_s`` in
the cell file, at about four fifths of the knee.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]


def sustained(records, t1: float, drain: float) -> bool:
    n = len(records)
    if any(r.first is None or r.first > t1 + drain for r in records):
        return False
    third = max(1, n // 3)
    from bench import stats
    head = [r.first - r.due for r in records[:third]]
    tail = [r.first - r.due for r in records[-third:]]
    return stats.percentile(tail, 50) <= 2 * stats.percentile(head, 50)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--drain", type=float, default=10.0)
    args = ap.parse_args(argv)
    from bench import cells
    cell = cells.load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: runs on the chip only", file=sys.stderr)
        return 3
    from bench import run as run_mod
    run_mod.enable_compile_cache(jax)
    from bench import harness, stats, traffic
    from repro.serving import PagedEngine
    compiles = harness.CompileStats()
    weights, eng = harness.build_engine(cell, args.seed)
    config, mesh = eng.config, eng.mesh
    for rate in args.rates:
        if eng is None:
            eng = PagedEngine(config, weights, mesh=mesh)
        reqs = traffic.generate(cell.traffic, rate, args.seconds, args.seed,
                                cell.config["vocab_size"])
        harness.warm(eng, cell.workload["warm"], reqs)
        win = harness.drive(eng, reqs, args.seconds, compiles)
        e2e = stats.end_to_end(win.records, win.t0, win.t1, win.deadline)
        c = win.counters
        print(json.dumps({
            "rate": rate, "requests": len(reqs),
            "sustained": sustained(win.records, win.t1, args.drain),
            "preemptions": win.preemptions,
            "compiles_in_window": win.compiles,
            "decode_step_ms": 1e3 * c["decode_s"] / max(1, c["decode_calls"]),
            "prefill_ms_per_1k_tok": 1e6 * c["prefill_s"]
            / max(1, c["prefill_tokens"]),
            "steps": c["steps"],
            "lateness": stats.lateness(win.records), **e2e}), flush=True)
        eng = None
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
