"""Operations and bytes that the algorithm needs, from a configuration's shapes.

Counts cover every matrix product of a serving step: the q/k/v/o projections,
the three FFN products, the vocabulary head, and attention's two products
(scores and values) over the context each token really has.  They use the
unpadded shapes: the real rows of a decode batch, the real tokens of a chunk,
a context of ``position + 1`` tokens, and the head only where a logit row is
needed (every decoded token, the last real row of a final chunk).  What a
program computes beyond that (padded lanes, masked cache slots, padded
weights) is not work here, so it shows as lost share.

Bytes are weights read once per call, the KV cache read over the real
context and written for the new tokens, and the matmuls' activations in and
out; all at the configuration's element size.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Shapes:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    elem_bytes: int = 2

    @classmethod
    def from_config(cls, c: dict) -> "Shapes":
        return cls(
            layers=c["num_hidden_layers"], d=c["hidden_size"],
            heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
            head_dim=c["head_dim"], ff=c["intermediate_size"], vocab=c["vocab_size"],
        )

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def layer_weights(self) -> int:
        """Weight elements of one layer's matrix products."""
        d = self.d
        return d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d + 3 * d * self.ff

    @property
    def head_weights(self) -> int:
        return self.d * self.vocab

    @property
    def kv_bytes_per_token(self) -> int:
        return self.layers * 2 * self.kv_dim * self.elem_bytes

    @property
    def act_bytes_per_token(self) -> int:
        """Activations into and out of one token's projections and FFN, all layers."""
        d, q, kv, ff = self.d, self.q_dim, self.kv_dim, self.ff
        per_layer = (d + q + 2 * kv) + (q + d) + 2 * (d + ff) + (ff + d)
        return self.layers * per_layer * self.elem_bytes

    def attn_flops(self, context: int) -> int:
        """Scores and weighted values of one token over ``context`` keys."""
        return self.layers * 4 * self.q_dim * context


def decode_work(s: Shapes, contexts: list[int]) -> tuple[float, float]:
    """One batched decode: a token per real lane, lane ``i`` attending over
    ``contexts[i]`` keys (its own included)."""
    n = len(contexts)
    flops = n * 2 * (s.layers * s.layer_weights + s.head_weights)
    flops += sum(s.attn_flops(c) for c in contexts)
    weights = (s.layers * s.layer_weights + s.head_weights) * s.elem_bytes
    kv = sum(contexts) * s.kv_bytes_per_token  # read (the new token's write included)
    acts = n * (s.act_bytes_per_token + (s.d + s.vocab) * s.elem_bytes)
    return float(flops), float(weights + kv + acts)


def chunk_work(s: Shapes, start: int, n: int, final: bool) -> tuple[float, float]:
    """One prefill chunk of ``n`` real tokens at positions ``[start, start+n)``;
    a final chunk also needs one row of logits."""
    # token at position t attends over t + 1 keys
    ctx_sum = n * start + n * (n + 1) // 2
    flops = n * 2 * s.layers * s.layer_weights + s.layers * 4 * s.q_dim * ctx_sum
    weights = s.layers * s.layer_weights * s.elem_bytes
    kv = (start + n) * s.kv_bytes_per_token  # context read + chunk written
    acts = n * s.act_bytes_per_token
    if final:
        flops += 2 * s.head_weights
        weights += s.head_weights * s.elem_bytes
        acts += (s.d + s.vocab) * s.elem_bytes
    return float(flops), float(weights + kv + acts)


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline: the larger of compute time and memory time at peak."""
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
