"""Operations and bytes that the work needs, computed from shapes, and the
chip's peaks.

These count what the algorithm must do, not what the program happens to
do: a decode step reads every weight once and the K/V of the live tokens
once, never the padded width of a block table; training counts the
forward and backward matmuls once, with no recomputation.  A roofline
share is the least time those would take at the chip's peaks, over the
time measured.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

# Published peaks of one chip, keyed by JAX's ``device_kind``.  Source:
# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM.
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       "to bench/harness/flops.py with its source")
    return PEAKS[device_kind]


@dataclass(frozen=True)
class Dense:
    """Shapes of a dense decoder (GQA attention + SwiGLU MLP)."""
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    qkv_bias: bool

    @classmethod
    def of(cls, c: Dict[str, Any]) -> "Dense":
        h = int(c["num_attention_heads"])
        return cls(layers=int(c["num_hidden_layers"]),
                   d=int(c["hidden_size"]), heads=h,
                   kv_heads=int(c["num_key_value_heads"]),
                   head_dim=int(c.get("head_dim")
                                or c["hidden_size"] // h),
                   d_ff=int(c["intermediate_size"]),
                   vocab=int(c["vocab_size"]),
                   tied=bool(c["tie_word_embeddings"]),
                   qkv_bias=bool(c.get("qkv_bias", False)))

    # -- parameter counts --------------------------------------------------
    @property
    def layer_matmul_params(self) -> int:
        q = self.d * self.heads * self.head_dim
        kv = 2 * self.d * self.kv_heads * self.head_dim
        o = self.heads * self.head_dim * self.d
        mlp = 3 * self.d * self.d_ff
        return q + kv + o + mlp

    @property
    def layer_params(self) -> int:
        bias = (self.heads + 2 * self.kv_heads) * self.head_dim \
            if self.qkv_bias else 0
        return self.layer_matmul_params + bias + 2 * self.d

    @property
    def params(self) -> int:
        emb = self.vocab * self.d * (1 if self.tied else 2)
        return self.layers * self.layer_params + emb + self.d

    @property
    def head_params(self) -> int:
        return self.vocab * self.d

    # -- FLOPs -------------------------------------------------------------
    def forward_flops_per_token(self, context: float) -> float:
        """One token's forward FLOPs (multiply-add = 2) attending over
        ``context`` keys: projections, MLP, attention scores and values,
        and the vocabulary head."""
        mm = 2 * (self.layers * self.layer_matmul_params + self.head_params)
        attn = 4 * self.layers * self.heads * self.head_dim * context
        return mm + attn

    def train_flops_per_token(self, seq: int) -> float:
        """Forward + backward (3x forward) FLOPs per token of causal
        training at sequence length ``seq``: a token attends over
        (seq + 1) / 2 keys on average.  No recomputation is counted."""
        return 3 * self.forward_flops_per_token((seq + 1) / 2)

    # -- bytes ---------------------------------------------------------------
    def kv_bytes_per_token(self, itemsize: int = 2) -> int:
        """K and V of one token over all layers."""
        return 2 * self.layers * self.kv_heads * self.head_dim * itemsize

    def decode_step(self, rows: int, kv_tokens: int, itemsize: int = 2
                    ) -> Dict[str, float]:
        """One batched decode step of ``rows`` live requests whose caches
        hold ``kv_tokens`` tokens in all (each row's own token included):
        every weight read once, the live K/V read once, the new K/V
        written once."""
        flops = rows * 2 * (self.layers * self.layer_matmul_params
                            + self.head_params) \
            + 4 * self.layers * self.heads * self.head_dim * kv_tokens
        weights = self.params * itemsize
        kv = kv_tokens * self.kv_bytes_per_token(itemsize) \
            + rows * self.kv_bytes_per_token(itemsize)
        return {"flops": float(flops), "bytes": float(weights + kv)}

    def paged_decode_kernel(self, rows: int, kv_tokens: int,
                            itemsize: int = 2) -> Dict[str, float]:
        """The fused paged-attention kernel over all layers of one decode
        step: q and out of each live row, and the live K/V, each moved
        once; scores and values computed over the live keys only."""
        per_layer_kv = kv_tokens * 2 * self.kv_heads * self.head_dim \
            * itemsize
        q_out = rows * 2 * self.heads * self.head_dim * itemsize
        flops = 4 * self.layers * self.heads * self.head_dim * kv_tokens
        return {"flops": float(flops),
                "bytes": float(self.layers * (per_layer_kv + q_out))}


def roofline_s(work: Dict[str, float], peak: Dict[str, float]) -> float:
    """Least time the chip needs for ``work``: the larger of its FLOPs at
    peak compute and its bytes at peak bandwidth."""
    return max(work["flops"] / peak["flops_bf16"],
               work["bytes"] / peak["hbm_bytes_per_s"])
