"""Decoder-stack model driver (dense / MoE / hybrid / xLSTM / VLM backbones).

All forward code is written in the *local-shard view* and expects to run inside
``jax.shard_map`` (launch/, serving/, training/ own that boundary).  With
``AxisCtx(tp_axis=None)`` the same code runs single-device (unit tests, oracles).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config import (BLOCK_SLSTM, ISOConfig, ModelConfig, padded_vocab)
from repro.core.chunking import split_chunks
from repro.core.iso import run_stack_decode, run_stack_prefill
from repro.core.overlap import AxisCtx, psum_now
from repro.layers import embeddings as emb_lib
from repro.layers import ssm as ssm_lib
from repro.layers import xlstm as xlstm_lib
from repro.layers.heads import head_layout
from repro.layers.norms import init_norm, norm
from repro.layers.rope import sinusoidal_embedding
from repro.models.blocks import StageCtx, init_block_params


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def pattern_periods(cfg: ModelConfig) -> int:
    n = len(cfg.block_pattern)
    assert cfg.num_layers % n == 0, (cfg.num_layers, cfg.block_pattern)
    return cfg.num_layers // n


def init_decoder_params(key, cfg: ModelConfig, tp: int, dtype=jnp.bfloat16) -> Dict:
    periods = pattern_periods(cfg)
    layout = head_layout(cfg.num_heads, max(cfg.num_kv_heads, 1), tp)
    k_emb, k_layers, k_norm = jax.random.split(key, 3)
    params: Dict[str, Any] = {
        "embed": emb_lib.init_embedding(k_emb, cfg, tp, dtype),
        "final_norm": init_norm(cfg.d_model, cfg.norm_type),
    }
    pos_params = []
    for i, kind in enumerate(cfg.block_pattern):
        keys = jax.random.split(jax.random.fold_in(k_layers, i), periods)
        stacked = jax.vmap(
            lambda k: init_block_params(k, cfg, kind, layout, tp, dtype))(keys)
        pos_params.append(stacked)
    params["periods"] = tuple(pos_params)
    return params


# ---------------------------------------------------------------------------
# forward helpers
# ---------------------------------------------------------------------------

def _stage_ctx(cfg: ModelConfig, ctx: AxisCtx, mode: str,
               lengths=None) -> StageCtx:
    layout = head_layout(cfg.num_heads, max(cfg.num_kv_heads, 1), ctx.tp)
    expert_offset = 0
    if cfg.moe is not None:
        e_loc = cfg.moe.padded_experts(ctx.tp) // ctx.tp
        expert_offset = ctx.axis_index() * e_loc
    return StageCtx(cfg=cfg, group_eff=layout.group_eff, tp=ctx.tp,
                    expert_offset=expert_offset, mode=mode,
                    window=cfg.sliding_window, lengths=lengths)


def embed_tokens(params, tokens, cfg: ModelConfig, ctx: AxisCtx, *, pos_offset=0):
    v_loc = params["embed"]["table"].shape[0]
    vocab_offset = ctx.axis_index() * v_loc
    e = emb_lib.embed_partial(params["embed"], tokens, vocab_offset)
    e = psum_now(e, ctx)
    if cfg.pos_type == "sinusoidal":
        S = tokens.shape[1]
        e = e + sinusoidal_embedding(S, cfg.d_model, pos_offset).astype(e.dtype)[None]
    return e


def _final(params, x, cfg):
    return norm(params["final_norm"], x, cfg.norm_type, cfg.rms_eps)


def _sinusoid_at(positions, d_model: int):
    """Sinusoidal embedding at traced per-request positions.  (B,) -> (B, D)."""
    half = d_model // 2
    inv = jnp.exp(-jnp.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / (half - 1))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# prefill (ISO lives here)
# ---------------------------------------------------------------------------

def prefill_chunk_lengths(S: int, iso: ISOConfig, cfg: ModelConfig,
                          tp: int) -> Tuple[int, ...]:
    """ISO chunk lengths of a prefill call over ``S`` positions."""
    if cfg.residual_wiring == "ladder":
        # ladder wiring supplies the overlap itself (stage k-1's reduce
        # hides behind stage k's compute); an ISO chunk interleave would
        # resolve each chunk's pending during the OTHER chunk's unit and
        # silently restore the standard wiring per chunk — single-chunk
        # schedule, always, so chunked/resumed grants stay function-equal
        return (S,)
    return split_chunks(S, iso, cfg, tp=tp)


def prefill(params, cfg: ModelConfig, ctx: AxisCtx, iso: ISOConfig, *,
            tokens=None, embeds=None, extra_embeds=None,
            logits_mode: str = "all", return_cache: bool = False,
            cache_len: int = 0, remat: bool = False, unroll: bool = False,
            layer_statics=None, mode: str = "prefill",
            prefix_caches=None, pos_offset=0,
            block_tables=None, prefix_lens=None, valid_len=None,
            return_extras: bool = False) -> Dict[str, Any]:
    """Run the stack over a full prompt — or one resumed slice of it — with the
    ISO schedule.

    tokens: (B,S) int32, or embeds: (B,S,D) precomputed (audio/vlm frontends).
    extra_embeds: (B,S0,D) prepended continuous tokens (VLM patches).

    Resumed chunked prefill (paged engine): ``prefix_caches`` is a per-position
    tuple of dicts stacked over periods — attention positions carry a gathered
    ``{k, v, pos}`` prefix (padded slots, pos -1 = empty), recurrent positions
    carry their ``{ssm|mlstm|slstm}`` state — and ``pos_offset`` (static int or
    traced scalar) is the absolute position of this call's first token.  The
    call's own chunking still happens here, so ISO overlap applies within the
    resumed slice exactly as in a monolithic prefill.

    Paged resumed prefill: when ``prefix_caches`` carries page pools
    (``k_pages``/``v_pages``) instead of a gathered dense prefix, pass
    ``block_tables`` (B, MB) and ``prefix_lens`` (B,) so attention reads the
    prefix in place through the paged flash-prefill kernel.  ``valid_len``
    (traced scalar) marks how many of this call's tokens are real — the
    bucket-padded tail beyond it is masked out of attention (grant-size
    bucketing; see serving/paged_engine.py).

    Batched multi-request grants: ``pos_offset``, ``prefix_lens`` and
    ``valid_len`` may all be per-row (B,) vectors — each row is one packed
    prefill grant resuming at its own absolute position with its own paged
    prefix (0 for a fresh request) and its own real-token count.  The ISO
    chunk split is over the shared (bucket-padded) call length, so the
    overlap schedule applies to the whole packed batch at once.
    """
    if embeds is None:
        embeds = embed_tokens(params, tokens, cfg, ctx)
        if cfg.pos_type == "sinusoidal" and not (isinstance(pos_offset, int)
                                                 and pos_offset == 0):
            raise NotImplementedError(
                "resumed prefill with sinusoidal positions (traced offset)")
    if extra_embeds is not None:
        embeds = jnp.concatenate([extra_embeds.astype(embeds.dtype), embeds], axis=1)
    B, S, D = embeds.shape

    lengths = prefill_chunk_lengths(S, iso, cfg, ctx.tp)
    ladder = cfg.residual_wiring == "ladder"
    starts, acc = [], 0
    for l in lengths:
        starts.append(acc)
        acc += l
    x_chunks = []
    off = 0
    for l in lengths:
        x_chunks.append(jax.lax.slice_in_dim(embeds, off, off + l, axis=1))
        off += l

    assert layer_statics is None or prefix_caches is None
    sctx = _stage_ctx(cfg, ctx, mode)
    sctx.pos_offset = pos_offset
    sctx.block_tables = block_tables
    sctx.lengths = prefix_lens
    sctx.valid_len = valid_len
    xs_final, extras = run_stack_prefill(
        params["periods"], cfg.block_pattern, x_chunks, tuple(starts), sctx, ctx,
        layer_statics=layer_statics if prefix_caches is None else prefix_caches,
        remat=remat, unroll=unroll, ladder=ladder)
    x = jnp.concatenate(xs_final, axis=1) if len(xs_final) > 1 else xs_final[0]
    x = _final(params, x, cfg)

    out: Dict[str, Any] = {"hidden": x, "num_chunks": len(lengths),
                           "chunk_lengths": lengths}
    if logits_mode == "all":
        out["logits_local"] = emb_lib.lm_head_local(params["embed"], x)
    elif logits_mode == "last":
        out["logits_local"] = emb_lib.lm_head_local(
            params["embed"], x[:, -1:, :])
    aux = 0.0
    for ex in extras:
        if "moe_aux" in ex:
            aux = aux + jnp.sum(ex["moe_aux"])
    out["moe_aux"] = aux
    if return_cache:
        out["caches"] = _build_caches(extras, cfg, B, S, cache_len or S, ctx)
    if return_extras:
        # raw per-position extras stacked over periods: kv_k/kv_v of the S new
        # tokens + final recurrent states — the paged engine scatters these
        out["extras"] = extras
    return out


def _build_caches(extras: Sequence[Dict], cfg: ModelConfig, B: int, S: int,
                  cache_len: int, ctx: AxisCtx):
    """Convert per-position prefill extras into decode caches."""
    caches = []
    for i, kind in enumerate(cfg.block_pattern):
        ex = extras[i]
        c: Dict[str, Any] = {}
        if "kv_k" in ex:
            k, v = ex["kv_k"], ex["kv_v"]              # (Pd,B,S,H,hd)
            Pd = k.shape[0]
            eff_len = min(cache_len, cfg.sliding_window) if cfg.sliding_window \
                else cache_len
            ck = jnp.zeros((Pd, B, eff_len, k.shape[3], k.shape[4]), k.dtype)
            cv = jnp.zeros_like(ck)
            cpos = jnp.full((Pd, B, eff_len), -1, jnp.int32)
            n_keep = min(S, eff_len)
            src_k = k[:, :, S - n_keep:]
            src_v = v[:, :, S - n_keep:]
            pos_vals = jnp.arange(S - n_keep, S, dtype=jnp.int32)
            slots = pos_vals % eff_len
            ck = ck.at[:, :, slots].set(src_k)
            cv = cv.at[:, :, slots].set(src_v)
            cpos = cpos.at[:, :, slots].set(
                jnp.broadcast_to(pos_vals, (Pd, B, n_keep)))
            c.update(k=ck, v=cv, pos=cpos)
        for sk in ("ssm", "mlstm", "slstm"):
            if sk in ex:
                c[sk] = ex[sk]
        caches.append(c)
    return tuple(caches)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

DECODE_SCHEDULES = ("sequential", "batch_split", "cross_block", "ladder",
                    "ladder_seq")


def decode_step(params, cfg: ModelConfig, ctx: AxisCtx, tokens, caches,
                lengths, unroll: bool = False, block_tables=None,
                decode_mask=None, overlap_batch: bool = False,
                kv_splits: int = 1,
                schedule: str = None) -> Tuple[jnp.ndarray, Any]:
    """tokens: (B,K) int32 — K=1 plain decode, K>1 a speculative verify
    window whose token qi sits at position ``lengths[b] + qi``; lengths:
    (B,) tokens already processed.

    Paged decode (flash-decode over the page pool): caches carry
    ``k_pages``/``v_pages`` per attention position and ``block_tables``
    (B, MB) maps positions to pages; ``decode_mask`` (B,) marks the slots
    really decoding (others scatter to the scratch page).  The K-token
    window runs through the same kernel grid (see kernels/flash_decode.py)
    and scatters all K positions' KV.  ``kv_splits`` (static) runs the
    paged attention's page walk as that many sequence-parallel spans
    (split-KV flash-decode) — it rides through StageCtx into every decode
    driver, orthogonal to the schedule.

    ``schedule`` picks the collective schedule (core/iso.py):

    * ``"sequential"`` — immediate reduce per stage (run_stack_decode);
    * ``"batch_split"`` — each batch half's reduce hides behind the other
      half's compute (run_stack_decode_overlap; falls back to sequential
      at B < 2);
    * ``"cross_block"`` — deferred reduces resolve at the next stage top,
      riding the scan carry across block boundaries (token-identical to
      sequential);
    * ``"ladder"`` / ``"ladder_seq"`` — the ladder-residual driver with
      deferred / immediate collectives (run_stack_decode_ladder).

    A ladder-wired config (``cfg.residual_wiring == "ladder"``) always runs
    the ladder driver — the wiring is part of the model function — with any
    non-sequential schedule mapping to deferred collectives.  Conversely,
    forcing ``schedule="ladder"`` on a standard-wired config runs the
    REWIRED function (the overlap probe uses this as a timing proxy; never
    serve with it).  ``overlap_batch=True`` is the legacy spelling of
    ``schedule="batch_split"``.

    Returns (logits_local (B,K,V_loc), updated caches).
    """
    if schedule is None:
        schedule = "batch_split" if overlap_batch else "sequential"
    assert schedule in DECODE_SCHEDULES, schedule
    K = tokens.shape[1]
    x = embed_tokens(params, tokens, cfg, ctx)
    if cfg.pos_type == "sinusoidal":
        # embed_tokens added position-0.. sinusoids; replace with per-request pos
        base = sinusoidal_embedding(K, cfg.d_model, 0).astype(jnp.float32)[None]
        pos = lengths[:, None] + jnp.arange(K)[None]
        per_req = jax.vmap(lambda p: _sinusoid_at(p, cfg.d_model))(pos)
        x = (x.astype(jnp.float32) - base + per_req).astype(x.dtype)
    sctx = _stage_ctx(cfg, ctx, "decode", lengths=lengths)
    sctx.block_tables = block_tables
    sctx.decode_mask = decode_mask
    sctx.kv_splits = kv_splits
    if cfg.residual_wiring == "ladder" or schedule in ("ladder", "ladder_seq"):
        from repro.core.iso import run_stack_decode_ladder
        x, new_caches = run_stack_decode_ladder(
            params["periods"], cfg.block_pattern, x, caches, sctx, ctx,
            unroll=unroll,
            defer=schedule not in ("sequential", "ladder_seq"))
    elif schedule == "batch_split":
        from repro.core.iso import run_stack_decode_overlap
        x, new_caches = run_stack_decode_overlap(
            params["periods"], cfg.block_pattern, x, caches, sctx, ctx,
            unroll=unroll)
    else:
        x, new_caches = run_stack_decode(params["periods"], cfg.block_pattern,
                                         x, caches, sctx, ctx, unroll=unroll,
                                         schedule=schedule)
    x = _final(params, x, cfg)
    logits = emb_lib.lm_head_local(params["embed"], x)
    return logits, new_caches


def init_caches(cfg: ModelConfig, batch: int, cache_len: int, tp: int,
                dtype=jnp.bfloat16):
    """Empty decode caches — GLOBAL (padded) shapes; cache_specs shards the kv
    head / SSM inner dims over the model axis (local views divide by tp)."""
    periods = pattern_periods(cfg)
    layout = head_layout(cfg.num_heads, max(cfg.num_kv_heads, 1), tp)
    hd = cfg.resolved_head_dim
    eff_len = min(cache_len, cfg.sliding_window) if cfg.sliding_window else cache_len
    caches = []
    for kind in cfg.block_pattern:
        c: Dict[str, Any] = {}
        if kind in ("attn_mlp", "attn_moe", "hybrid", "dec_block"):
            hkv = layout.hkv_eff
            c["k"] = jnp.zeros((periods, batch, eff_len, hkv, hd), dtype)
            c["v"] = jnp.zeros((periods, batch, eff_len, hkv, hd), dtype)
            c["pos"] = jnp.full((periods, batch, eff_len), -1, jnp.int32)
        if kind == "hybrid":
            inner = ssm_lib.inner_dim(cfg.d_model, cfg.ssm, tp)
            c["ssm"] = ssm_lib.SSMState(
                conv=jnp.zeros((periods, batch, cfg.ssm.conv_dim - 1, inner),
                               dtype),
                h=jnp.zeros((periods, batch, inner, cfg.ssm.state_dim),
                            jnp.float32))
        if kind == "mlstm":
            # GLOBAL state: (B,H,hd_k,hd_v) — cache_specs shards hd_v over TP
            hdk = cfg.d_model // cfg.num_heads
            st = xlstm_lib.init_mlstm_state(batch, cfg.num_heads, hdk, hdk)
            c["mlstm"] = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (periods,) + a.shape).copy(), st)
        if kind == "slstm":
            st = xlstm_lib.init_slstm_state(batch, cfg.d_model)
            c["slstm"] = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (periods,) + a.shape).copy(), st)
        caches.append(c)
    return tuple(caches)


# ---------------------------------------------------------------------------
# PartitionSpecs for the shard_map boundary
# ---------------------------------------------------------------------------

def _leaf_spec(path, leaf, batch_axes) -> P:
    names = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
    name = names[-1]
    parents = set(names[:-1])
    nd = leaf.ndim
    stacked = "periods" in str(path)         # layer-stacked leaves get leading None

    def w(spec):                             # prepend the period-stacking dim
        return P(*( (None,) + tuple(spec) )) if stacked else P(*spec)

    if "slstm" in parents:                   # sLSTM weights are fully replicated
        return w((None,) * (nd - (1 if stacked else 0)))
    if name in ("table", "head"):
        return P("model", None)
    if name in ("wq", "wk", "wv"):
        return w((None, "model", None))
    if name == "wo":
        return w(("model", None, None))
    if name in ("w_up", "w_gate", "w_down"):
        if nd - (1 if stacked else 0) == 3:  # MoE expert-stacked
            return w(("model", None, None))
        return w((None, "model")) if name != "w_down" else w(("model", None))
    if name == "router":
        return w((None, None))
    if name in ("w_v", "w_og"):              # mlstm value path: shard feature dim
        return w((None, None, "model"))
    if name == "w_out":
        if nd - (1 if stacked else 0) == 3:  # mlstm (H, hd_loc, D)
            return w((None, "model", None))
        return w(("model", None))            # ssm (inner_loc, D)
    if name in ("w_x", "w_z", "w_dt", "conv_w"):
        return w((None, "model"))
    if name in ("dt_bias", "d_skip"):
        return w(("model",))
    if name == "a_log":
        return w(("model", None))
    # everything else (norms, gates, slstm, w_b/w_c, biases): replicated
    return w((None,) * (nd - (1 if stacked else 0)))


def decoder_param_specs(params_shape, batch_axes=("data",)):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _leaf_spec(path, leaf, batch_axes), params_shape)


def cache_specs(caches_shape, batch_axes=("data",), shard_batch: bool = True):
    b = batch_axes if shard_batch else None

    def spec(path, leaf):
        names = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
        nd = leaf.ndim
        last = names[-1] if names else ""
        if last in ("k", "v", "cross_k", "cross_v"):
            return P(None, b, None, "model", None)
        if last == "pos":
            return P(None, b, None)
        if "ssm" in names:                   # SSMState leaves (P,B,*,inner_loc*)
            if nd == 4 and "conv" in str(path):
                return P(None, b, None, "model")
            return P(None, b, "model", None)
        if "mlstm" in names:
            if nd == 5:                      # c: (P,B,H,hdk,hdv_loc)
                return P(None, b, None, None, "model")
            return P(*( (None, b) + (None,) * (nd - 2) ))
        return P(*( (None, b) + (None,) * (nd - 2) ))

    return jax.tree_util.tree_map_with_path(spec, caches_shape)
