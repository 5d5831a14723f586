"""Operations and bytes that the algorithm needs, from a mixture-of-experts
GQA configuration at a chip's expert share (a configuration file names this
module with ``"work": "moe_gqa"``).

Counts cover every matrix product of a serving step: the q/k/v/o
projections, the router, the expert SwiGLU of the assignments that land on
the held experts, the vocabulary head, and attention's two products over the
context each token really has.  They use the unpadded shapes (the real rows
of a decode batch, the real tokens of a chunk), as ``dense_gqa`` does.

Routing is data: the counts are its expectation under uniform routing,
which a router of random weights gives to tokens of distinct requests (the
lanes of a decode).  Of ``n`` tokens, each choosing ``top_k`` of
``experts``, the held experts get ``n * top_k * held / experts``
assignments, and a held expert gets none with probability
``(1 - top_k / experts) ** n``.  Operations count the assignments; weight
bytes count the held experts that get at least one (an expert without a
token is not read).  The tokens of one chunk share a context, and past the
first layer a random router sends them to a few experts: for a chunk the
count is an upper bound on the experts read.
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
    expert_ff: int
    experts: int
    top_k: int
    held: int
    vocab: int
    elem_bytes: int = 2

    @classmethod
    def from_config(cls, c: dict) -> "Shapes":
        return cls(
            layers=c["num_hidden_layers"], d=c["hidden_size"],
            heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
            head_dim=c["head_dim"], expert_ff=c["moe_intermediate_size"],
            experts=c["num_experts"], top_k=c["num_experts_per_tok"],
            held=c["num_experts_held"], vocab=c["vocab_size"],
        )

    @property
    def q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def dense_weights(self) -> int:
        """Weight elements of one layer's products other than the experts':
        attention's four projections and the router."""
        d = self.d
        return 2 * d * self.q_dim + 2 * d * self.kv_dim + d * self.experts

    @property
    def expert_weights(self) -> int:
        return 3 * self.d * self.expert_ff

    @property
    def kv_bytes_per_token(self) -> int:
        return self.layers * 2 * self.kv_dim * self.elem_bytes

    def held_assignments(self, n: int) -> float:
        return n * self.top_k * self.held / self.experts

    def held_experts_used(self, n: int) -> float:
        return self.held * (1.0 - (1.0 - self.top_k / self.experts) ** n)


def expert_work(s: Shapes, n: int) -> tuple[float, float]:
    """The expert GEMMs of ``n`` tokens, every layer: operations of the
    assignments on held experts, bytes of the held experts with a token plus
    the rows in and out of the three products."""
    a = s.held_assignments(n)
    flops = s.layers * a * 2 * s.expert_weights
    rows = a * (2 * s.d + 3 * s.expert_ff) * s.elem_bytes  # x in, gate/up out, h in, y out
    return float(flops), float(s.layers * (s.held_experts_used(n) * s.expert_weights
                                           * s.elem_bytes + rows))


def _dense(s: Shapes, n: int) -> tuple[float, float]:
    """Attention projections and router of ``n`` tokens, every layer."""
    acts = n * s.layers * (2 * s.d + 2 * s.q_dim + 2 * s.kv_dim + s.experts) * s.elem_bytes
    return (float(s.layers * n * 2 * s.dense_weights),
            float(s.layers * s.dense_weights * s.elem_bytes + acts))


def decode_work(s: Shapes, contexts: list[int]) -> tuple[float, float]:
    """One batched decode: a token per real lane, lane ``i`` attending over
    ``contexts[i]`` keys (its own included)."""
    n = len(contexts)
    (fd, bd), (fe, be) = _dense(s, n), expert_work(s, n)
    flops = fd + fe + n * 2 * s.d * s.vocab
    flops += sum(s.layers * 4 * s.q_dim * c for c in contexts)
    head = (s.d * s.vocab + n * (s.d + s.vocab)) * s.elem_bytes
    kv = sum(contexts) * s.kv_bytes_per_token
    return float(flops), float(bd + be + head + kv)


def chunk_work(s: Shapes, start: int, n: int, final: bool) -> tuple[float, float]:
    """One prefill chunk of ``n`` real tokens at positions ``[start, start+n)``;
    a final chunk also needs one row of logits."""
    ctx_sum = n * start + n * (n + 1) // 2
    (fd, bd), (fe, be) = _dense(s, n), expert_work(s, n)
    flops = fd + fe + s.layers * 4 * s.q_dim * ctx_sum
    nbytes = bd + be + (start + n) * s.kv_bytes_per_token
    if final:
        flops += 2 * s.d * s.vocab
        nbytes += (s.d * s.vocab + s.d + s.vocab) * s.elem_bytes
    return float(flops), float(nbytes)
