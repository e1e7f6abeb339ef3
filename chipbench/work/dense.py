"""Work counts of the dense decoder (attention + SwiGLU MLP)."""

from __future__ import annotations

BF16 = 2


def layer_params(c: dict) -> int:
    """Matmul parameters of one layer: q, k, v, o and the three MLP
    matrices."""
    d, H, KH, hd = c["d_model"], c["n_heads"], c["n_kv_heads"], c["head_dim"]
    return d * H * hd + 2 * d * KH * hd + H * hd * d + 3 * d * c["d_ff"]


def matmul_params(c: dict) -> int:
    return c["n_layers"] * layer_params(c) + c["d_model"] * c["vocab_size"]


def param_bytes(c: dict) -> int:
    """Every parameter read once, at bfloat16: matmuls and norm scales."""
    L, d = c["n_layers"], c["d_model"]
    return BF16 * (matmul_params(c) + 2 * L * d + d)


def kv_bytes_per_position(c: dict) -> int:
    return c["n_layers"] * 2 * c["n_kv_heads"] * c["head_dim"] * BF16


def decode_step(c: dict, contexts) -> tuple:
    """One token for each sequence; ``contexts`` lists each sequence's
    live positions, the new one included."""
    L, H, hd, d = c["n_layers"], c["n_heads"], c["head_dim"], c["d_model"]
    B, live = len(contexts), sum(contexts)
    flops = 2 * B * matmul_params(c) + 4 * L * H * hd * live
    kv = kv_bytes_per_position(c)
    byts = param_bytes(c) + B * d * BF16 + kv * live + kv * B
    return flops, byts


def prefill(c: dict, batch: int, length: int) -> tuple:
    """``batch`` prompts of ``length`` tokens, causal attention, the head
    at the last position only."""
    L, H, hd, d = c["n_layers"], c["n_heads"], c["head_dim"], c["d_model"]
    tokens = batch * length
    pairs = batch * length * (length + 1) // 2
    flops = (2 * tokens * L * layer_params(c) + 4 * L * H * hd * pairs
             + 2 * batch * d * c["vocab_size"])
    byts = (param_bytes(c) + tokens * d * BF16
            + tokens * kv_bytes_per_position(c))
    return flops, byts
