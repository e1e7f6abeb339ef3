"""Work counts of the Zamba2 hybrid decoder: Mamba-2 layers with grouped
B/C, and shared attention+MLP blocks applied at the sites of its layer
pattern, each site with its own linear, adapter and K/V cache."""

from __future__ import annotations

BF16, F32 = 2, 4


def dims(c: dict):
    s, d = c["ssm"], c["d_model"]
    di = s["expand"] * d
    return (di, di // s["headdim"], s["headdim"], s["d_state"], s["d_conv"],
            s.get("ngroups", 1))


def n_sites(c: dict) -> int:
    return sum(i < c["n_layers"] for i in c["hybrid_layer_ids"])


def mamba_params(c: dict) -> int:
    """Matmul parameters of one Mamba layer: the z, x, B, C (per group) and
    dt projections in and the projection out."""
    d = c["d_model"]
    di, nh, _, ns, _, G = dims(c)
    return d * (2 * di + 2 * G * ns + nh) + di * d


def block_params(c: dict) -> int:
    """Matmul parameters of one shared block: q, k, v over [x, embedding]
    (2·d wide), o, and the gated MLP's three matrices."""
    d, H, KH, hd = c["d_model"], c["n_heads"], c["n_kv_heads"], c["head_dim"]
    return 2 * d * (H + 2 * KH) * hd + H * hd * d + 3 * d * c["d_ff"]


def site_params(c: dict) -> int:
    """Matmul parameters a site holds alone: its linear and its adapter."""
    d, r = c["d_model"], c["adapter_rank"]
    return d * d + r * (d + 2 * c["d_ff"])


def _mamba_other(c: dict) -> int:
    """A Mamba layer's parameters outside its matmuls: conv taps and
    biases, dt_bias, A_log, D, the gated norm and the pre-norm."""
    di, nh, _, ns, taps, G = dims(c)
    return (taps + 1) * (di + 2 * G * ns) + 3 * nh + di + c["d_model"]


def param_bytes(c: dict) -> int:
    """Parameters read by one pass at bfloat16: every Mamba layer once,
    each shared block once per site that applies it, every site's own
    parameters, and the tied embedding once as the head."""
    L, d, S = c["n_layers"], c["d_model"], n_sites(c)
    block = block_params(c) + 3 * d                    # + its two norms
    return BF16 * (L * (mamba_params(c) + _mamba_other(c))
                   + S * (block + site_params(c)) + d * c["vocab_size"] + d)


def kv_bytes_per_position(c: dict) -> int:
    return n_sites(c) * 2 * c["n_kv_heads"] * c["head_dim"] * BF16


def state_bytes(c: dict) -> int:
    """Recurrent state of one sequence: SSM state in float32, the
    convolution's last taps-1 inputs in bfloat16."""
    di, nh, hp, ns, taps, G = dims(c)
    return c["n_layers"] * (nh * hp * ns * F32
                            + (taps - 1) * (di + 2 * G * ns) * BF16)


def _per_token_flops(c: dict) -> int:
    """Everything but attention's context and the head for one position:
    the matmuls, the convolution and the recurrence (decay and update,
    then the read-out)."""
    di, nh, hp, ns, taps, G = dims(c)
    L, S = c["n_layers"], n_sites(c)
    return (2 * (L * mamba_params(c) + S * (block_params(c) + site_params(c)))
            + L * (2 * taps * (di + 2 * G * ns) + 4 * nh * hp * ns))


def _attn_flops(c: dict, pairs: int) -> int:
    """Scores and values of every site over ``pairs`` (query, key) pairs."""
    return 4 * n_sites(c) * c["n_heads"] * c["head_dim"] * pairs


def decode_step(c: dict, contexts) -> tuple:
    """One token for each sequence; ``contexts`` lists each sequence's live
    positions, the new one included. The state is read and written once."""
    B, live = len(contexts), sum(contexts)
    d, kv = c["d_model"], kv_bytes_per_position(c)
    flops = (B * (_per_token_flops(c) + 2 * d * c["vocab_size"])
             + _attn_flops(c, live))
    byts = (param_bytes(c) + B * d * BF16 + kv * live + kv * B
            + 2 * B * state_bytes(c))
    return flops, byts


def prefill(c: dict, batch: int, length: int) -> tuple:
    """``batch`` prompts of ``length`` tokens: causal attention at each
    site, the linear recurrence, the head at the last position only; the
    K/V of every position and the final state are written once."""
    tokens = batch * length
    pairs = batch * length * (length + 1) // 2
    d = c["d_model"]
    flops = (tokens * _per_token_flops(c) + _attn_flops(c, pairs)
             + 2 * batch * d * c["vocab_size"])
    byts = (param_bytes(c) + tokens * d * BF16
            + tokens * kv_bytes_per_position(c) + batch * state_bytes(c))
    return flops, byts


def ssd_intra_chunk(c: dict, batch: int, length: int) -> tuple:
    """What the SSD kernel computes for one layer: per chunk the C·Bᵀ
    scores once per group, and per head the masked product with x and the
    chunk's state. Inputs x, B, C at bfloat16 and dt at float32; outputs
    (the diagonal-block y, the chunk states and the decays) at float32."""
    di, nh, hp, ns, _, G = dims(c)
    cl = c["ssm"]["chunk"]
    chunks = batch * -(-length // cl)
    flops = chunks * (G * 2 * cl * cl * ns
                      + nh * (2 * cl * cl * hp + 2 * cl * hp * ns))
    pos = chunks * cl
    byts = (pos * nh * hp * BF16 + pos * nh * F32 + 2 * pos * G * ns * BF16
            + pos * nh * hp * F32 + chunks * nh * hp * ns * F32
            + pos * nh * F32)
    return flops, byts
