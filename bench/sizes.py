"""A configuration file's sizes, in the form the benchmark's own code
uses (weights, reference, counts).  The keys are those of the published
``config.json``."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Sizes:
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    n_layers: int
    rope_theta: float
    norm_eps: float

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def layer_matmul_params(self) -> int:
        """q, k, v, o and the three SwiGLU matrices of one layer."""
        hd = self.head_dim
        attn = 2 * self.d_model * self.n_heads * hd \
            + 2 * self.d_model * self.n_kv_heads * hd
        return attn + 3 * self.d_model * self.d_ff

    @property
    def matmul_params(self) -> int:
        """Every parameter that takes part in a matrix product per token:
        the layers and the LM head (the embedding is a gather)."""
        return self.n_layers * self.layer_matmul_params \
            + self.d_model * self.vocab

    @property
    def params(self) -> int:
        """All parameters: the matmul ones, the embedding and the norms."""
        return self.matmul_params + self.vocab * self.d_model \
            + (2 * self.n_layers + 1) * self.d_model


def from_config(c: dict) -> Sizes:
    if c.get("model_type") != "llama":
        raise ValueError(f"only dense llama-architecture configurations "
                         f"are modelled here, not {c.get('model_type')!r}")
    return Sizes(d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
                 n_kv_heads=c["num_key_value_heads"],
                 d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                 n_layers=c["num_hidden_layers"],
                 rope_theta=float(c["rope_theta"]),
                 norm_eps=float(c["rms_norm_eps"]))
