"""The paged Pallas kernels compile for a TPU v5e at the widths served.

Each test lowers a kernel with ``interpret=False`` for a described (not
attached) v5e chip and compiles it with the TPU compiler, then checks that the
kernel is in the program as a TPU custom call.  Widths are qwen3-4b's: head_dim
128, bf16 pools, 8 KV heads on one chip and 2 (its TP=4 share) per chip.

The engine's own decode and prefill programs are compiled here too, to check
that the page writer leaves the KV pool in its own layout, and at Qwen3-8B's
widths at tensor parallel 4 over a described 2x2 host, to count the
all-reduces the ISO schedule puts in each program.

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
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.config import (Config, ISOConfig, ModelConfig, ParallelConfig,
                          RuntimeConfig, ServingConfig)
from repro.kernels import backend
from repro.kernels.flash_decode import flash_decode
from repro.kernels.flash_prefill_paged import flash_prefill_paged
from repro.models import api
from repro.models.decoder import decoder_param_specs, prefill_chunk_lengths
from repro.serving import PagedEngine
from repro.serving.kvstate import KVPool
from repro.serving.paged_engine import KV_POOL_SPEC

HD, GROUP, B, MB, N = 128, 4, 4, 64, 257


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
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


# ---------------------------------------------------------------------------
# tensor parallel 4: the all-reduces of the ISO schedule
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tp4_engine(topo):
    """A PagedEngine at Qwen3-8B widths with 2 layers, sharded over a (1, 4)
    mesh of the described 2x2 host, with the docs cell's page size, slots
    and row length (516 table pages).  Its own pool is a few pages on the
    CPU (a described chip holds no array); the programs are lowered for the
    cell's 5,400 pages, placed as the engine places them."""
    mesh = jax.make_mesh((1, 4), ("data", "model"), devices=topo.devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = ModelConfig(name="qwen3-8b-2l", family="dense", num_layers=2,
                      d_model=4096, num_heads=32, num_kv_heads=8,
                      head_dim=128, d_ff=12288, vocab_size=151936,
                      qk_norm=True, tie_embeddings=False)
    config = Config(
        model=cfg, parallel=ParallelConfig(data=1, model=4),
        iso=ISOConfig(enabled=True, num_chunks=2, min_chunk_tokens=256,
                      chunk_align=128),
        runtime=RuntimeConfig(mode="serve"),
        serving=ServingConfig(page_size=16, max_batch=24, max_len=8256,
                              num_pages=5400, prefill_token_budget=2048))
    params = jax.eval_shape(
        lambda: api.init_params(jax.random.PRNGKey(0), cfg, 4))
    pool = KVPool.create(cfg, 8, 16, tp=4)
    return PagedEngine(config, params, mesh=mesh, kv_pool=pool)


def _tp4_program_text(eng, program):
    mesh = eng.mesh
    rep = lambda shape, dt: jax.ShapeDtypeStruct(          # noqa: E731
        shape, dt, sharding=NamedSharding(mesh, P()))
    params = jax.tree_util.tree_map(
        lambda a, spec: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, spec)),
        eng.params, decoder_param_specs(eng.params),
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    k = eng.kv.arrays["k"][0]
    pool = jax.ShapeDtypeStruct((k.shape[0], 5401) + k.shape[2:], k.dtype,
                                sharding=NamedSharding(mesh, KV_POOL_SPEC))
    kv = {"k": (pool,) * len(eng.kv.arrays["k"]),
          "v": (pool,) * len(eng.kv.arrays["v"]),
          "pos": rep((5401, eng.ps), jnp.int32)}
    B, MB = eng.max_batch, eng.max_blocks
    kind, a, b = program
    if kind == "decode":                 # (K, S)
        fn = eng._get_decode(a, b)
        states = jax.tree_util.tree_map(lambda x: rep(x.shape, x.dtype),
                                        eng.states)
        args = (params, rep((B, a), jnp.int32), rep((B, MB), jnp.int32),
                rep((B,), jnp.int32), kv, states, rep((B,), jnp.bool_))
    else:                                # (T, all_fresh), one row
        fn = eng._get_prefill_batched(a, 1, b)
        args = (params, rep((1, a), jnp.int32), kv, rep((1, MB), jnp.int32),
                rep((1,), jnp.int32), rep((1,), jnp.int32))
    return fn.lower(*args).compile().as_text()


def _all_reduces(text):
    """All-reduce ops per computation of compiled HLO text."""
    out, name = {}, None
    for line in text.splitlines():
        m = re.match(r"^(ENTRY )?%?([\w.-]+) ", line)
        if m and not line.startswith(" "):
            name = m.group(2)
        if re.search(r" all-reduce(-start)?\(", line):
            out[name] = out.get(name, 0) + 1
    return out


@pytest.mark.parametrize("program,stages", [
    (("prefill", 512, True), 2), (("prefill", 512, False), 2),
    (("decode", 1, 1), 2)], ids=["prefill_fresh", "prefill_resumed",
                                 "decode_batch_split"])
def test_tp4_programs_hold_the_iso_all_reduces(tp4_engine, program, stages,
                                               monkeypatch):
    """The layer loop's body holds one all-reduce per reducing stage
    (attention, MLP) per ISO unit: per chunk in prefill (a 512-token call
    runs in two chunks), per half of the batch in batch-split decode.
    Outside the loop: the vocabulary-sharded embedding's reduce and the
    trailing reduce of the last unit."""
    monkeypatch.setattr(backend, "default_interpret", lambda: False)
    eng = tp4_engine
    assert eng._decode_schedule == "batch_split"
    if program[0] == "prefill":
        units = len(prefill_chunk_lengths(program[1], eng.config.iso,
                                          eng.cfg, eng.tp))
        assert units == 2
    else:
        units = 2
    counts = _all_reduces(_tp4_program_text(eng, program))
    assert sorted(counts.values()) == [2, stages * units], counts
