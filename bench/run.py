#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``: each
number the correctness check compared, beside its limit.  Earlier lines on
standard error report set-up, compiles inside the window, how late the
generator ran, and the same checks.  With no TPU, or fewer chips than the
cell asks for, it exits 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
CACHE_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache(jax) -> str:
    """Persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else a fixed directory in the checkout.  Every program is kept,
    however quick its compile, so a second run compiles nothing.  Nothing
    is evicted: eviction takes a file lock on every read, and the warm-up's
    threads then time out on it and compile again."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the profiler trace to this directory")
    args = ap.parse_args(argv)

    from bench import cells
    cell = cells.load_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX finds no TPU (platform {devices[0].platform!r});"
              f" the benchmark runs on the chip only", file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} chips, JAX finds"
              f" {len(devices)}", file=sys.stderr)
        return 3
    cache = enable_compile_cache(jax)
    from bench import harness
    harness.log("device", platform=devices[0].platform,
                kind=repr(devices[0].device_kind), count=len(devices),
                jax=jax.__version__, cache_dir=cache)
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         devices[:cell.chips], T_START,
                         keep_trace=args.keep_trace)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
