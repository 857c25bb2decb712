"""Model shapes that ML traffic synthesis (``scenarios.ml``) sizes from.

A training step of an architecture puts bytes on the wire in proportion to
its parameter and activation sizes.  This module keeps only the published
dimensions those sizes come from, for the architectures the catalog names:

* ``qwen2-1.5b`` — dense, GQA.  28 layers, d_model 1536, 12 heads (2 KV),
  head_dim 128, d_ff 8960, vocab 151936.  Qwen2 technical report
  (arXiv:2407.10671), HF ``Qwen/Qwen2-1.5B``.
* ``gemma3-4b`` — dense, GQA.  34 layers, d_model 2560, 8 heads (4 KV),
  head_dim 256, d_ff 10240, vocab 262144.  Gemma 3 technical report
  (arXiv:2503.19786), HF ``google/gemma-3-4b-pt``.  Its MLP is counted
  as two matrices (``act="gelu"``), not as Gemma's gated three.
* ``qwen3-moe-30b-a3b`` — MoE, 128 experts, top-8.  48 layers, d_model
  2048, 32 heads (4 KV), head_dim 128, expert d_ff 768, vocab 151936.
  Qwen3 technical report (arXiv:2505.09388), HF ``Qwen/Qwen3-30B-A3B``.
"""
from __future__ import annotations

from dataclasses import dataclass

VOCAB_PAD = 128              # embedding rows are padded to this multiple


@dataclass(frozen=True)
class ModelConfig:
    name: str
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int                    # per expert for MoE configs
    vocab_size: int
    num_experts: int = 0
    experts_per_token: int = 0
    act: str = "swiglu"          # swiglu (3 MLP matrices) | gelu (2)

    def layer_param_count(self) -> int:
        """Parameters of one decoder block: attention projections plus the
        MLP (every expert and the router for MoE configs)."""
        d, ff = self.d_model, self.d_ff
        attn = (d * self.num_heads * self.head_dim
                + 2 * d * self.num_kv_heads * self.head_dim
                + self.num_heads * self.head_dim * d)
        if self.num_experts:
            mlp = d * self.num_experts + 3 * d * ff * self.num_experts
        else:
            mlp = (3 if self.act == "swiglu" else 2) * d * ff
        return attn + mlp

    def embed_param_count(self) -> int:
        """Embedding-table parameters over the padded vocabulary."""
        rows = -(-self.vocab_size // VOCAB_PAD) * VOCAB_PAD
        return rows * self.d_model


MODELS = {cfg.name: cfg for cfg in (
    ModelConfig("qwen2-1.5b", num_layers=28, d_model=1536, num_heads=12,
                num_kv_heads=2, head_dim=128, d_ff=8960, vocab_size=151936),
    ModelConfig("gemma3-4b", num_layers=34, d_model=2560, num_heads=8,
                num_kv_heads=4, head_dim=256, d_ff=10240, vocab_size=262144,
                act="gelu"),
    ModelConfig("qwen3-moe-30b-a3b", num_layers=48, d_model=2048,
                num_heads=32, num_kv_heads=4, head_dim=128, d_ff=768,
                vocab_size=151936, num_experts=128, experts_per_token=8),
)}


def get_model(name: str) -> ModelConfig:
    if name not in MODELS:
        raise KeyError(f"unknown arch {name!r}; have {sorted(MODELS)}")
    return MODELS[name]
