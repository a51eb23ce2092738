"""The paged Pallas kernels compile for a TPU v5e at the widths served.

Each test lowers a kernel with ``interpret=False`` for a described (not
attached) v5e chip and compiles it with the TPU compiler, then checks that the
kernel is in the program as a TPU custom call.  Widths are qwen3-4b's: head_dim
128, bf16 pools, 8 KV heads on one chip and 2 (its TP=4 share) per chip.

The engine's own decode and prefill programs are compiled here too, to check
that the page writer leaves the KV pool in its own layout.

All chip compiles of the test suite live in this one file.  The topology is
described inside a module-scoped fixture, never at import, so that every test
worker collects the same tests and only the worker running this file loads the
TPU compiler.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import (Config, ISOConfig, ModelConfig, ParallelConfig,
                          RuntimeConfig, ServingConfig)
from repro.kernels import backend
from repro.kernels.flash_decode import flash_decode
from repro.kernels.flash_prefill_paged import flash_prefill_paged
from repro.models import api
from repro.serving import PagedEngine

HD, GROUP, B, MB, N = 128, 4, 4, 64, 257


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(one_chip, kernel, ps, hkv, **kw):
    s = lambda shape, dt: _shape(one_chip, shape, dt)      # noqa: E731
    pool = s((N, hkv, ps, HD), jnp.bfloat16)
    bt, lens = s((B, MB), jnp.int32), s((B,), jnp.int32)
    if kernel == "prefill":
        q = s((B, hkv * GROUP, 256, HD), jnp.bfloat16)
        fn = jax.jit(lambda q, k, v, bt, ln: flash_prefill_paged(
            q, k, v, bt, ln, ln, interpret=False, **kw))
    else:
        q = s((B, hkv * GROUP, HD), jnp.bfloat16)
        fn = jax.jit(lambda q, k, v, bt, ln: flash_decode(
            q, k, v, bt, ln, interpret=False, **kw))
    return fn.lower(q, pool, pool, bt, lens).compile().as_text()


@pytest.mark.parametrize("hkv", [8, 2])
@pytest.mark.parametrize("ps", [16, 32, 64])
@pytest.mark.parametrize("kernel,kw,names", [
    ("decode", {}, ["flash_decode"]),
    ("decode", {"kv_splits": 4}, ["flash_decode", "flash_decode_reduce"]),
    ("prefill", {}, ["flash_prefill_paged"]),
], ids=["decode", "decode_split4", "prefill"])
def test_paged_kernel_compiles_for_v5e(one_chip, kernel, kw, names, ps, hkv):
    text = _compiled_text(one_chip, kernel, ps, hkv, **kw)
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for name in names:
        assert any(f"%{name}" in line for line in calls), (name, calls)


def test_page_below_packed_bf16_tile_compiles(one_chip):
    """The (ps, hd) page block spans the pool's trailing dims, so the TPU
    lowering takes a page smaller than bf16's 16-row packed tile."""
    text = _compiled_text(one_chip, "decode", 8, 8)
    assert "tpu_custom_call" in text


def test_prefill_rejects_unaligned_query_block(one_chip):
    """A query block that is not a multiple of 8 rows is refused up front
    when the chunk spans several blocks — the TPU lowering's own rule."""
    with pytest.raises(ValueError, match="block_q"):
        _compiled_text(one_chip, "prefill", 16, 8, block_q=12)


# ---------------------------------------------------------------------------
# the engine's programs keep the KV pool in its own layout
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def chat_engine():
    """A PagedEngine at qwen3-4b widths with 2 layers and a cut vocabulary,
    holding the chat cell's pool: 300 pages of 64 tokens, 16 slots."""
    cfg = ModelConfig(name="qwen3-4b-2l", family="dense", num_layers=2,
                      d_model=2560, num_heads=32, num_kv_heads=8,
                      head_dim=128, d_ff=9728, vocab_size=1024, qk_norm=True,
                      tie_embeddings=True)
    config = Config(
        model=cfg, parallel=ParallelConfig(data=1, model=1),
        iso=ISOConfig(enabled=True, num_chunks=2, min_chunk_tokens=256,
                      chunk_align=128),
        runtime=RuntimeConfig(mode="serve"),
        serving=ServingConfig(page_size=64, max_batch=16, max_len=4608,
                              num_pages=300, prefill_token_budget=512))
    params = jax.eval_shape(
        lambda: api.init_params(jax.random.PRNGKey(0), cfg, 1))
    return PagedEngine(config, params)


def _engine_program_text(one_chip, eng, program):
    """Compiled text of one engine closure, lowered for the described chip
    with its real (not interpreted) kernels."""
    s = lambda shape, dt: _shape(one_chip, shape, dt)      # noqa: E731
    like = lambda tree: jax.tree_util.tree_map(            # noqa: E731
        lambda a: s(a.shape, a.dtype), tree)
    params, kv = like(eng.params), like(eng.kv.arrays)
    B, MB = eng.max_batch, eng.max_blocks
    kind, a, b = program
    if kind == "decode":                 # (K, S)
        fn = eng._get_decode(a, b)
        args = (params, s((B, a), jnp.int32), s((B, MB), jnp.int32),
                s((B,), jnp.int32), kv, like(eng.states), s((B,), jnp.bool_))
    else:                                # (rows, all_fresh), 256-token rows
        fn = eng._get_prefill_batched(256, a, b)
        args = (params, s((a, 256), jnp.int32), kv, s((a, MB), jnp.int32),
                s((a,), jnp.int32), s((a,), jnp.int32))
    return fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("program", [
    ("decode", 1, 1), ("decode", 1, 4), ("decode", 3, 1),
    ("prefill", 2, True), ("prefill", 2, False),
], ids=["decode_k1_s1", "decode_k1_s4", "verify_k3_s1", "prefill_fresh",
        "prefill_resumed"])
def test_engine_programs_keep_pool_layout(one_chip, chat_engine, program,
                                          monkeypatch):
    """Every array shaped like the stacked pool ``(Pd, N+1, Hkv, ps, hd)`` or
    like one layer's pool has the pool's own row-major layout: the page
    writer's update windows are contiguous in it, so no program relayouts
    the pool around the write."""
    monkeypatch.setattr(backend, "default_interpret", lambda: False)
    text = _engine_program_text(one_chip, chat_engine, program)
    stacked = chat_engine.kv.arrays["k"][0].shape
    own = {stacked: "4,3,2,1,0", stacked[1:]: "3,2,1,0"}
    seen = {}
    for dims, layout in re.findall(r"bf16\[([\d,]+)\]\{([\d,]+)", text):
        shape = tuple(int(d) for d in dims.split(","))
        if shape in own:
            seen.setdefault((shape, layout), 0)
            seen[(shape, layout)] += 1
    assert any(shape == stacked for shape, _ in seen), "no pool in the text"
    wrong = {k: n for k, n in seen.items() if k[1] != own[k[0]]}
    assert not wrong, f"pool-sized arrays relayouted: {wrong}"
