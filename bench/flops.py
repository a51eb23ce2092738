"""Operations and bytes that the work of a step needs, from shapes and
resident lengths alone.

These are the algorithm's counts, not the program's: a padded token, a
padded row, a page read twice or a logits row computed and thrown away add
nothing.  So the least time they allow, against the peaks of
``peaks.json``, is a floor under any implementation, and a share of it
cannot pass 100% unless a count here is too high.

Counts are for the whole model over all chips; a reader divides by the
chips the cell uses.  Attention counts two multiply-adds per query, key and
head dimension (scores and values), each two operations.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Sequence, Tuple


@dataclass(frozen=True)
class Shape:
    layers: int
    d_model: int
    q_heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    dtype_bytes: int = 2           # weights, activations and KV in bf16

    @classmethod
    def from_config(cls, c: dict) -> "Shape":
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   q_heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"])

    @property
    def layer_matmul_params(self) -> int:
        """Weights one token multiplies in one layer: q, k, v, o and the
        three SwiGLU matrices."""
        d, hd = self.d_model, self.head_dim
        attn = d * hd * (2 * self.q_heads + 2 * self.kv_heads)
        return attn + 3 * d * self.d_ff

    def attn_flops(self, pairs: int) -> int:
        """Scores and values for ``pairs`` (query, key) pairs, one layer."""
        return 4 * self.q_heads * self.head_dim * pairs

    def kv_bytes(self, tokens: int) -> int:
        """K and V of ``tokens`` positions, one layer."""
        return 2 * tokens * self.kv_heads * self.head_dim * self.dtype_bytes


def causal_pairs(start: int, n: int) -> int:
    """(query, key) pairs when n queries at positions start..start+n-1
    each attend every key up to and including their own position."""
    return n * start + n * (n + 1) // 2


# ---------------------------------------------------------------------------
# whole model
# ---------------------------------------------------------------------------

def prefill_model_flops(s: Shape, start: int, n: int,
                        samples: bool) -> int:
    """One prefill grant: n prompt tokens after ``start`` resident ones;
    ``samples`` when the grant ends the prompt and its last position's
    logits are used."""
    f = s.layers * (2 * s.layer_matmul_params * n
                    + s.attn_flops(causal_pairs(start, n)))
    return f + (2 * s.d_model * s.vocab if samples else 0)


def decode_model_flops(s: Shape, resident: int) -> int:
    """One decoded token that attends ``resident`` cached keys and itself."""
    return (s.layers * (2 * s.layer_matmul_params
                        + s.attn_flops(resident + 1))
            + 2 * s.d_model * s.vocab)


# ---------------------------------------------------------------------------
# kernels (all layers of one step)
# ---------------------------------------------------------------------------

def flash_decode_work(s: Shape, resident: Sequence[int]) -> Tuple[int, int]:
    """(flops, bytes) of the paged decode kernels of one decode step: every
    active row reads its resident K/V once and attends it with one query
    token; q is read and the fp32 output written once.  ``resident``: the
    cached tokens of each active row (the new token's own key is merged
    outside the kernel)."""
    tokens = sum(resident)
    qo = len(resident) * s.q_heads * s.head_dim * (s.dtype_bytes + 4)
    return (s.layers * s.attn_flops(tokens),
            s.layers * (s.kv_bytes(tokens) + qo))


def flash_prefill_paged_work(s: Shape, grants: Iterable[Tuple[int, int]]
                             ) -> Tuple[int, int]:
    """(flops, bytes) of the paged prefill kernels of one step: each
    resumed grant (start > 0, n tokens) attends its ``start`` resident
    tokens from the pool with all n queries.  A fresh grant reads no pool."""
    flops = byts = 0
    for start, n in grants:
        if start <= 0 or n <= 0:
            continue
        flops += s.attn_flops(n * start)
        byts += s.kv_bytes(start) + n * s.q_heads * s.head_dim * (
            s.dtype_bytes + 4)
    return s.layers * flops, s.layers * byts


def least_seconds(flops: int, byts: int, peak_flops: float,
                  peak_bytes_per_s: float, chips: int) -> Tuple[float, str]:
    """Least time the work allows on ``chips`` chips, and its bound."""
    t_c = flops / (peak_flops * chips)
    t_m = byts / (peak_bytes_per_s * chips)
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
