"""Plain float32 reference of a dense Qwen3 decoder (Qwen3ForCausalLM).

Straight from the published architecture: token embedding; per layer
RMSNorm, q/k/v projections, RMSNorm of each query and key head (q_norm,
k_norm), rotary embedding (rotate-half, theta from the config), causal
grouped-query attention with scale head_dim**-0.5, output projection,
residual add, RMSNorm, SwiGLU MLP (silu(x W_gate) * x W_up) W_down, residual
add; final RMSNorm; logits against the output matrix, or the embedding when
``tie_word_embeddings``.  No bias anywhere.

Every matrix product runs in float32 at HIGHEST precision.  It imports
nothing of the program: it reads the benchmark's weights (bench/weights.py)
by their names, one layer at a time, upcasting that layer only, so it fits
beside the bf16 weights.  Attention runs in query blocks so a long sequence
never builds its whole score matrix.

``precision="fp8"`` is the control: every matrix product's operands are
rounded to float8 e4m3 first (weights per output channel, activations per
row, each scaled so its largest magnitude maps to 448), accumulating in
float32, as an fp8 serving path would.
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0
PAD_TO = 512              # sequence lengths compile in steps of this
Q_BLOCK = 512


def _fq(x, axes):
    """Round x to float8 e4m3, scaled per slice over ``axes``."""
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec: str, a, b, fp8: bool, a_axes, b_axes):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if fp8:
        a, b = _fq(a, a_axes), _fq(b, b_axes)
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    v = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(v + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """x: (S, H, D); rotate-half rotary embedding at positions pos (S,)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, lw: Dict, l, *, eps, theta, group, fp8):
    """One decoder layer over the whole sequence x (S, D), float32."""
    S = x.shape[0]
    at = {k: v[l] for k, v in lw["attn"].items()}
    h = _rms(x, lw["norm1"]["scale"][l], eps)
    q = _mm("sd,dhk->shk", h, at["wq"], fp8, -1, 0)
    k = _mm("sd,dhk->shk", h, at["wk"], fp8, -1, 0)
    v = _mm("sd,dhk->shk", h, at["wv"], fp8, -1, 0)
    q = _rms(q, at["q_norm"], eps)
    k = _rms(k, at["k_norm"], eps)
    pos = jnp.arange(S)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    hd = q.shape[-1]
    k = jnp.repeat(k, group, axis=1)           # query head h reads h // group
    v = jnp.repeat(v, group, axis=1)
    nb = S // Q_BLOCK

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        s = _mm("qhd,khd->hqk", qb, k, fp8, -1, -1) * hd ** -0.5
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("hqk,khd->qhd", p, v, fp8, -1, 0)

    o = jax.lax.map(block, jnp.arange(nb)).reshape(S, *q.shape[1:])
    x = x + _mm("shk,hkd->sd", o, at["wo"], fp8, (-2, -1), (0, 1))
    m = lw["mlp"]
    h = _rms(x, lw["norm2"]["scale"][l], eps)
    g = _mm("sd,df->sf", h, m["w_gate"][l], fp8, -1, 0)
    u = _mm("sd,df->sf", h, m["w_up"][l], fp8, -1, 0)
    return x + _mm("sf,fd->sd", jax.nn.silu(g) * u, m["w_down"][l], fp8,
                   -1, 0)


@functools.lru_cache(maxsize=8)
def _programs(eps: float, theta: float, group: int, vocab: int, fp8: bool):
    layer = jax.jit(functools.partial(_layer, eps=eps, theta=theta,
                                      group=group, fp8=fp8))

    @jax.jit
    def embed(table, tokens):
        return table[tokens].astype(jnp.float32)

    @jax.jit
    def head(x, scale, out_w, rows):
        h = _rms(x[rows], scale, eps)
        return _mm("sd,vd->sv", h, out_w[:vocab], fp8, -1, -1)

    return embed, layer, head


def logits(weights, config: Dict, tokens: np.ndarray,
           rows: Sequence[int], precision: str = "fp32") -> np.ndarray:
    """Float32 logits (len(rows), vocab) at the given positions of one
    sequence, computed over all of ``tokens`` with causal attention."""
    assert precision in ("fp32", "fp8"), precision
    S = len(tokens)
    S_pad = -(-S // PAD_TO) * PAD_TO          # pad tail: causal, never read
    toks = np.zeros(S_pad, np.int32)
    toks[:S] = tokens
    group = config["num_attention_heads"] // config["num_key_value_heads"]
    embed, layer, head = _programs(float(config["rms_norm_eps"]),
                                   float(config["rope_theta"]), group,
                                   int(config["vocab_size"]),
                                   precision == "fp8")
    emb = weights["embed"]
    lw = weights["periods"][0]
    heads = (lw["attn"]["wq"].shape[-2], lw["attn"]["wk"].shape[-2])
    if heads != (config["num_attention_heads"],
                 config["num_key_value_heads"]):
        raise ValueError(f"weights hold {heads} query/KV head slots, the "
                         f"config states {config['num_attention_heads']}/"
                         f"{config['num_key_value_heads']}: padded or "
                         f"replicated heads are not modelled here")
    x = embed(emb["table"], jnp.asarray(toks))
    for l in range(config["num_hidden_layers"]):
        x = layer(x, lw, jnp.int32(l))
    out_w = emb["table"] if config["tie_word_embeddings"] else emb["head"]
    out = head(x, weights["final_norm"]["scale"], out_w,
               jnp.asarray(np.asarray(rows, np.int32)))
    return np.asarray(out, np.float32)
