#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, taken on the chip.

    python bench/control.py --workload <cell> --seconds <s> \
        --seeds 1 2 ... --control-seeds 1 2 3

For each seed, in one process: the cell's set-up and a window of
``--seconds`` at the cell's own load, then the sampled requests' widest
logit gap of the served tokens against the float32 reference (the lower
reading), and for the control seeds the same gap of the tokens a float8
reference puts first at the same positions (the upper reading).  The
benchmark's own runs never run this.  One JSON line per seed on standard
output, then a summary line.
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


def readings(cell, seed: int, seconds: float, control: bool,
             compiles=None) -> dict:
    """One seed's program reading and, with ``control``, its float8
    control reading, at the cell's size and load."""
    from bench import cells as cells_lib
    from bench import correct, harness, traffic
    w = cell.workload
    reqs = traffic.generate(cell.traffic, float(w["rate_per_s"]), seconds,
                            seed, cell.config["vocab_size"])
    weights, eng = harness.build_engine(cell, seed)
    harness.warm(eng, w["warm"], reqs)
    win = harness.drive(eng, reqs, seconds,
                        compiles or harness.CompileStats(),
                        finished_tokens=w["correct"]["min_tokens"])
    done = {i: list(r.tokens) for i, r in enumerate(win.records)
            if i in win.states and win.states[i].done}
    del eng
    gc.collect()
    spec = w["correct"]
    lengths = {i: len(reqs[i].prompt) + reqs[i].max_new for i in done}
    picked = correct.sample(lengths, {i: len(done[i]) for i in done}, seed,
                            spec["max_requests"], spec["min_tokens"])
    ref = cells_lib.reference(cell)
    out = {"seed": seed, "requests": len(picked),
           "tokens": sum(len(done[i]) for i in picked),
           "program_gap": 0.0, "control_gap": None}
    for i in picked:
        g = correct.served_gaps(ref, weights, cell.config, reqs[i].prompt,
                                done[i])
        out["program_gap"] = max(out["program_gap"], float(g.max()))
        if control:
            c = correct.served_gaps(ref, weights, cell.config,
                                    reqs[i].prompt, done[i], "fp8")
            out["control_gap"] = max(out["control_gap"] or 0.0,
                                     float(c.max()))
    del weights
    gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    from bench import cells
    cell = cells.load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: runs on the chip only", file=sys.stderr)
        return 3
    from bench import run as run_mod
    run_mod.enable_compile_cache(jax)
    from bench import harness
    compiles = harness.CompileStats()
    rows = []
    for s in args.seeds:
        t0 = time.perf_counter()
        r = readings(cell, s, args.seconds, s in args.control_seeds,
                     compiles)
        r["wall_s"] = time.perf_counter() - t0
        rows.append(r)
        print(json.dumps(r), flush=True)
    prog = [r["program_gap"] for r in rows]
    ctrl = [r["control_gap"] for r in rows if r["control_gap"] is not None]
    print(json.dumps({"lower_reading": max(prog),
                      "upper_reading": min(ctrl) if ctrl else None,
                      "seeds": len(rows), "control_seeds": len(ctrl)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
