"""Work counts of the Mamba-2 decoder."""

from __future__ import annotations

BF16, F32 = 2, 4


def dims(c: dict):
    s, d = c["ssm"], c["d_model"]
    di = s["expand"] * d
    return di, di // s["headdim"], s["headdim"], s["d_state"], s["d_conv"]


def layer_params(c: dict) -> int:
    """Matmul parameters of one layer: the z, x, B, C, dt projections in
    and the projection out."""
    d = c["d_model"]
    di, nh, _, ns, _ = dims(c)
    return d * (2 * di + 2 * ns + nh) + di * d


def matmul_params(c: dict) -> int:
    return c["n_layers"] * layer_params(c) + c["d_model"] * c["vocab_size"]


def param_bytes(c: dict) -> int:
    """Every parameter read once, at bfloat16."""
    L, d = c["n_layers"], c["d_model"]
    di, nh, _, ns, taps = dims(c)
    per_layer = layer_params(c) + taps * (di + 2 * ns) + 3 * nh + di
    return BF16 * (L * per_layer + d * c["vocab_size"] + d)


def state_bytes(c: dict) -> int:
    """Recurrent state of one sequence: SSM state in float32, the
    convolution's last taps-1 inputs in bfloat16."""
    di, nh, hp, ns, taps = dims(c)
    return c["n_layers"] * (nh * hp * ns * F32
                            + (taps - 1) * (di + 2 * ns) * BF16)


def _per_token_flops(c: dict) -> int:
    """Everything but the head for one position: projections, the
    convolution and the recurrence (decay and update, then the read-out)."""
    di, nh, hp, ns, taps = dims(c)
    return c["n_layers"] * (2 * layer_params(c) + 2 * taps * (di + 2 * ns)
                            + 4 * nh * hp * ns)


def decode_step(c: dict, contexts) -> tuple:
    """One token for each sequence; the state does not grow with the
    context."""
    B = len(contexts)
    flops = B * (_per_token_flops(c) + 2 * c["d_model"] * c["vocab_size"])
    byts = (param_bytes(c) + B * c["d_model"] * BF16
            + 2 * B * state_bytes(c))
    return flops, byts


def prefill(c: dict, batch: int, length: int) -> tuple:
    """``batch`` prompts of ``length`` tokens by the linear recurrence, the
    head at the last position only; the final state is written once."""
    tokens = batch * length
    flops = (tokens * _per_token_flops(c)
             + 2 * batch * c["d_model"] * c["vocab_size"])
    byts = (param_bytes(c) + tokens * c["d_model"] * BF16
            + batch * state_bytes(c))
    return flops, byts


def ssd_intra_chunk(c: dict, batch: int, length: int) -> tuple:
    """What the SSD kernel computes for one layer: per chunk the C·Bᵀ
    scores (shared by the heads) and per head the masked product with x
    and the chunk's state. Inputs x, B, C at bfloat16 and dt at float32;
    outputs (the diagonal-block y, the chunk states and the decays) at
    float32."""
    di, nh, hp, ns, _ = dims(c)
    cl = c["ssm"]["chunk"]
    nc = -(-length // cl)
    chunks = batch * nc
    flops = chunks * (2 * cl * cl * ns
                      + nh * (2 * cl * cl * hp + 2 * cl * hp * ns))
    pos = chunks * cl
    byts = (pos * nh * hp * BF16 + pos * nh * F32 + 2 * pos * ns * BF16
            + pos * nh * hp * F32 + chunks * nh * hp * ns * F32
            + pos * nh * F32)
    return flops, byts
