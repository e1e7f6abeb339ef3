"""The work counts against hand calculations at both configurations' real
sizes."""

import pytest

from chipbench.spec import HERE, load_json
from chipbench.work import dense, ssm

LM = load_json(HERE / "configs" / "stablelm-1.6b.json")
MAMBA = load_json(HERE / "configs" / "mamba2-130m.json")

# stablelm-1.6b: q, k, v, o are 2048 x 2048 each (32 heads of 64); the MLP
# is three 2048 x 5632 matrices; 24 layers; head 2048 x 100352
LM_LAYER = 4 * 2048 * 2048 + 3 * 2048 * 5632          # 51,380,224
LM_MATMUL = 24 * LM_LAYER + 2048 * 100352              # 1,438,646,272
LM_KV_POS = 24 * 2 * 32 * 64 * 2                       # 196,608 B

# mamba2-130m: d_inner 1536, 24 heads of 64, d_state 128; in-projection
# 768 x (1536 z + 1536 x + 128 B + 128 C + 24 dt), out-projection 1536 x 768
MB_LAYER = 768 * (1536 + 1536 + 128 + 128 + 24) + 1536 * 768   # 3,753,984
MB_MATMUL = 24 * MB_LAYER + 768 * 50280                        # 128,710,656
MB_STATE = 24 * (24 * 64 * 128 * 4 + 3 * (1536 + 2 * 128) * 2)  # 19,132,416


def test_dense_parameters():
    assert LM_LAYER == 51_380_224 and LM_MATMUL == 1_438_646_272
    assert dense.layer_params(LM) == LM_LAYER
    assert dense.matmul_params(LM) == LM_MATMUL
    # norms: two per layer and the final one, 2048 wide
    assert dense.param_bytes(LM) == 2 * (LM_MATMUL + 49 * 2048)
    assert dense.kv_bytes_per_position(LM) == LM_KV_POS


def test_dense_decode_step_at_live_context():
    # 8 sequences, each with 576 live positions
    flops, byts = dense.decode_step(LM, [576] * 8)
    assert flops == 2 * 8 * LM_MATMUL + 2 * 2 * 24 * 32 * 64 * 576 * 8
    assert byts == (2 * (LM_MATMUL + 49 * 2048) + 8 * 2048 * 2
                    + LM_KV_POS * 576 * 8 + LM_KV_POS * 8)
    # about 3.8 GB a step, of which 2.88 GB are the bf16 parameters
    assert 3.7e9 < byts < 3.9e9


def test_dense_prefill_heads_once_per_request():
    flops, byts = dense.prefill(LM, 8, 512)
    causal_pairs = 8 * (512 * 513 // 2)
    assert flops == (2 * 8 * 512 * 24 * LM_LAYER
                     + 2 * 2 * 24 * 32 * 64 * causal_pairs
                     + 2 * 8 * 2048 * 100352)
    assert 10.2e12 < flops < 10.4e12
    assert byts == (2 * (LM_MATMUL + 49 * 2048) + 8 * 512 * 2048 * 2
                    + 8 * 512 * LM_KV_POS)


def test_ssm_parameters_and_state():
    assert MB_LAYER == 3_753_984 and MB_MATMUL == 128_710_656
    assert ssm.layer_params(MAMBA) == MB_LAYER
    assert ssm.matmul_params(MAMBA) == MB_MATMUL
    assert ssm.state_bytes(MAMBA) == MB_STATE
    # conv taps, A_log, D, dt_bias and the gate norm per layer; final norm
    per_layer_extra = 4 * (1536 + 256) + 3 * 24 + 1536
    assert ssm.param_bytes(MAMBA) == 2 * (MB_MATMUL + 24 * per_layer_extra
                                          + 768)


def _per_token(layers=24):
    return layers * (2 * MB_LAYER + 2 * 4 * (1536 + 256)
                     + 4 * 24 * 64 * 128)


def test_ssm_decode_step_reads_and_writes_the_state():
    flops, byts = ssm.decode_step(MAMBA, [300] * 128)
    assert flops == 128 * (_per_token() + 2 * 768 * 50280)
    assert byts == ssm.param_bytes(MAMBA) + 128 * 768 * 2 + \
        2 * 128 * MB_STATE
    # 4.9 GB of state moved a step at batch 128
    assert 4.8e9 < 2 * 128 * MB_STATE < 5.0e9


def test_ssm_prefill_is_linear_in_length():
    flops, byts = ssm.prefill(MAMBA, 8, 2048)
    assert flops == 8 * 2048 * _per_token() + 2 * 8 * 768 * 50280
    assert byts == (ssm.param_bytes(MAMBA) + 8 * 2048 * 768 * 2
                    + 8 * MB_STATE)


@pytest.mark.parametrize("length,chunks", [(2048, 8), (512, 2), (300, 2)])
def test_ssd_intra_chunk(length, chunks):
    flops, byts = ssm.ssd_intra_chunk(MAMBA, 8, length)
    n = 8 * chunks                    # (batch, chunk) blocks of 256
    assert flops == n * (2 * 256 * 256 * 128
                         + 24 * (2 * 256 * 256 * 64 + 2 * 256 * 64 * 128))
    pos = n * 256
    assert byts == (pos * 24 * 64 * 2 + pos * 24 * 4 + 2 * pos * 128 * 2
                    + pos * 24 * 64 * 4 + n * 24 * 64 * 128 * 4
                    + pos * 24 * 4)
