"""Operation and byte counts (bench/flops.py) against hand counts."""
import json

import pytest

import bench_tiny
from bench import flops

S = flops.Shape(layers=2, d_model=8, q_heads=4, kv_heads=2, head_dim=4,
                d_ff=16, vocab=100)


def test_shape_from_published_config():
    c = json.loads((bench_tiny.BENCH / "configs" / "qwen3-4b.json")
                   .read_text())
    s = flops.Shape.from_config(c)
    # q 2560x4096, k and v 2560x1024, o 4096x2560, MLP 3 x 2560x9728
    assert s.layer_matmul_params == (2560 * 4096 * 2 + 2560 * 1024 * 2
                                     + 3 * 2560 * 9728)


def test_matmul_params_by_hand():
    # q: 8x16, k: 8x8, v: 8x8, o: 16x8, MLP: 3 x 8x16
    assert S.layer_matmul_params == 128 + 64 + 64 + 128 + 384


def test_causal_pairs():
    assert flops.causal_pairs(0, 3) == 1 + 2 + 3
    assert flops.causal_pairs(5, 2) == 6 + 7


def test_prefill_and_decode_model_flops():
    per_layer = 2 * 768 * 3 + 4 * 4 * 4 * (10 * 3 + 6)
    assert flops.prefill_model_flops(S, 10, 3, samples=False) == 2 * per_layer
    assert flops.prefill_model_flops(S, 10, 3, samples=True) == \
        2 * per_layer + 2 * 8 * 100
    assert flops.decode_model_flops(S, 9) == \
        2 * (2 * 768 + 64 * 10) + 2 * 8 * 100


def test_decode_kernel_work():
    f, b = flops.flash_decode_work(S, [3, 5])
    assert f == 2 * 4 * 4 * 4 * 8
    kv = 2 * 8 * 2 * 4 * 2               # k and v of 8 tokens, 2 heads, bf16
    qo = 2 * 4 * 4 * (2 + 4)             # two rows: bf16 q, fp32 out
    assert b == 2 * (kv + qo)


def test_prefill_kernel_work_counts_resumed_grants_only():
    assert flops.flash_prefill_paged_work(S, [(0, 64)]) == (0, 0)
    f, b = flops.flash_prefill_paged_work(S, [(0, 64), (32, 2)])
    assert f == 2 * 4 * 4 * 4 * 64
    assert b == 2 * (2 * 32 * 2 * 4 * 2 + 2 * 4 * 4 * 6)


def test_least_seconds_names_its_bound():
    t, bound = flops.least_seconds(100, 10, 10.0, 10.0, 1)
    assert (t, bound) == (10.0, "compute")
    t, bound = flops.least_seconds(10, 100, 10.0, 10.0, 2)
    assert (t, bound) == (pytest.approx(5.0), "memory")
