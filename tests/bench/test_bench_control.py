"""The control, at a size a test run holds: the float8 reference put in the
program's place fails the limit that the program's own served tokens meet
(bench/correct.py; the chip readings that set the real limits are in
PERF.md).  The engine serves a fixed set of requests to completion, so the
readings do not depend on the host's speed."""
import numpy as np
import pytest

import bench_tiny
from bench import cells, correct, traffic, weights


def _readings(cell, seed):
    from repro.serving import PagedEngine, Request
    from repro.serving.requests import SamplingParams
    cfg = cells.model_config(cell)
    w = weights.make_weights(cfg, 1, seed)
    eng = PagedEngine(cells.engine_config(cell, cfg), w)
    reqs = traffic.generate(cell.traffic, 8.0, 1.0, seed,
                            cell.config["vocab_size"])
    rids = [eng.add_request(Request(prompt=r.prompt, sampling=SamplingParams(
        max_new_tokens=r.max_new, eos_id=-1))) for r in reqs]
    out = eng.run_until_complete()
    ref = cells.reference(cell)
    prog, ctrl = [], []
    for r, rid in zip(reqs, rids):
        prog.append(correct.served_gaps(ref, w, cell.config, r.prompt,
                                        out[rid]).max())
        ctrl.append(correct.served_gaps(ref, w, cell.config, r.prompt,
                                        out[rid], "fp8").max())
    return float(np.max(prog)), float(np.max(ctrl)), sum(map(len, out.values()))


@pytest.mark.parametrize("seed", [2 ** 32 + 9, 3])
def test_float8_control_fails_where_the_program_passes(tmp_path, seed):
    cell = cells.load_cell("tiny.chat", bench_tiny.make_root(tmp_path))
    prog, ctrl, tokens = _readings(cell, seed)
    limit = cell.workload["correct"]["logit_gap_max"]
    assert tokens >= cell.workload["correct"]["min_tokens"]
    assert prog <= limit < ctrl, (prog, ctrl)
