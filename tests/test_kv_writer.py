"""The page writer ``kvcache.scatter_pages`` against a numpy reference.

The reference writes token by token into the head-major pool exactly as a
``pages.at[:, page, :, off].set(...)`` scatter does.  Coordinates come from
the same helpers the callers use (``window_page_coords`` for decode windows,
``token_page_coords`` for prefill grants), with pad and inactive tokens
routed to the scratch page; every non-scratch page must come out bit-equal.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving.kvcache import (scatter_pages, token_page_coords,
                                   window_page_coords)

PD, N, HKV, PS, HD = 2, 12, 2, 8, 16
SCRATCH = N                 # the pool holds N usable pages and the scratch
MB = 6


def _reference(pages, page, off, kv):
    """pages (Pd, N+1, Hkv, ps, hd); page/off (*I); kv (Pd, *I, Hkv, hd)."""
    out = np.array(pages)
    for idx in np.ndindex(*page.shape):
        out[:, page[idx], :, off[idx]] = kv[(slice(None),) + idx]
    return out


def _tables(rng, ends):
    """Disjoint block tables of random pages, each covering positions
    ``[0, end)``, -1 padded."""
    pages = iter(rng.permutation(N))
    bt = np.full((len(ends), MB), -1, np.int32)
    for r, end in enumerate(ends):
        for j in range(-(-end // PS)):
            bt[r, j] = next(pages)
    return bt


def _decode(rng, k_tokens, lengths, active):
    bt = _tables(rng, [n + k_tokens for n in lengths])
    page, off, _, _ = window_page_coords(
        jnp.asarray(lengths, jnp.int32), jnp.asarray(bt), k_tokens, PS,
        scratch=SCRATCH, decode_mask=jnp.asarray(active))
    return np.asarray(page), np.asarray(off)


def _grant(rng, starts, n_reals, T):
    bt = _tables(rng, [s + n for s, n in zip(starts, n_reals)])
    page, off = [], []
    for r, (start, n_real) in enumerate(zip(starts, n_reals)):
        p, o = token_page_coords(start + jnp.arange(T, dtype=jnp.int32),
                                 jnp.asarray(bt[r]), PS, SCRATCH)
        page.append(np.where(np.arange(T) < n_real, np.asarray(p), SCRATCH))
        off.append(np.asarray(o))
    return np.stack(page), np.stack(off)


CASES = {
    # one decode token per row
    "decode_k1": lambda rng: _decode(rng, 1, [3, 8, 15], [True] * 3),
    # a K=3 verify window from position 7 crosses into the second page
    "verify_k3_straddle": lambda rng: _decode(rng, 3, [7, 14, 2],
                                              [True] * 3),
    # a fresh grant of 20 tokens from position 0
    "grant_fresh": lambda rng: _grant(rng, [0], [20], 20),
    # a resumed grant that starts mid-page
    "grant_resumed_mid_page": lambda rng: _grant(rng, [5, 11], [9, 12], 12),
    # a bucket-padded grant whose pad tail goes to the scratch page
    "grant_pad_tail": lambda rng: _grant(rng, [3, 0], [6, 13], 16),
    # inactive decode rows write only the scratch page
    "decode_inactive_rows": lambda rng: _decode(rng, 2, [4, 9, 6, 1],
                                                [True, False, True, False]),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("case", list(CASES))
def test_scatter_pages_matches_token_reference(case, dtype):
    rng = np.random.default_rng(sorted(CASES).index(case))
    page, off = CASES[case](rng)
    pages = jnp.asarray(rng.standard_normal((PD, N + 1, HKV, PS, HD)), dtype)
    kv = jnp.asarray(rng.standard_normal((PD,) + page.shape + (HKV, HD)),
                     dtype)
    got = jax.jit(scatter_pages)(pages, jnp.asarray(page), jnp.asarray(off),
                                 kv)
    assert got.shape == pages.shape and got.dtype == pages.dtype
    want = _reference(np.asarray(pages), page, off, np.asarray(kv))
    np.testing.assert_array_equal(np.asarray(got)[:, :SCRATCH],
                                  want[:, :SCRATCH])
    if case == "decode_inactive_rows":
        # the inactive rows' tokens changed nothing outside the scratch page
        live = page != SCRATCH
        assert live[[1, 3]].sum() == 0 and live[[0, 2]].all()
