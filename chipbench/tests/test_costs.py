"""Kernel bytes and model FLOPs (the architecture's, `archs/`) against
hand counts."""
import json

from chipbench import costs, registry


def test_xor_kernel_bytes_k4():
    # four 1 MiB data units in, one 1 MiB parity unit out
    assert costs.xor_kernel_bytes(4, 1 << 20) == 5 * (1 << 20)
    # a ragged row is rounded up to whole int32 words
    assert costs.xor_kernel_bytes(4, 10) == 5 * 12


def test_raid5_kernel_bytes_k4():
    # 3 rounds of parity written: each XORs 4 units into 1
    assert costs.raid5_kernel_bytes(3 << 20, 4) == 15 << 20
    # 2 units rebuilt: 3 surviving data units + parity in, 1 out, each
    assert costs.raid5_kernel_bytes(2 << 20, 4) == 10 << 20


def test_ckpt_parity_kernel_bytes():
    ssz, k, piece = 4, 3, 1 << 20
    # 10 bytes over 3 columns of 4-byte units: columns of 4, 4, 2 bytes
    assert costs.ckpt_parity_kernel_bytes(10, ssz, k, piece) == 4 * 4
    # less than one unit: one column, one row XORed into the parity
    assert costs.ckpt_parity_kernel_bytes(3, ssz, k, piece) == 2 * 4
    # pieces of whole rounds: 24 bytes in pieces of 12 (two 3 x 4 rounds)
    assert costs.ckpt_parity_kernel_bytes(24, ssz, k, 12) == 2 * (4 * 4)
    assert costs.ckpt_parity_kernel_bytes(0, ssz, k, piece) == 0
    # one stripe: the data itself is the parity's only row
    assert costs.ckpt_parity_kernel_bytes(10, ssz, 1, piece) == 2 * 12


def test_train_flops_per_token_qwen3_4b_l5():
    with open(registry.HERE / "configs" / "qwen3-4b-l5.json") as f:
        cfg = json.load(f)
    seq = 1024
    d, ff, v, n = 2560, 9728, 151936, 5
    q, kv = 32 * 128, 8 * 128
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * ff
    assert per_layer == 100_925_440
    matmul = 2 * (n * per_layer + d * v)
    attn = n * 4 * 32 * 128 * (seq + 1) / 2
    want = 3 * (matmul + attn)
    assert registry.arch(cfg).train_flops_per_token(cfg, seq) == want
    # about 6 x 894M parameters
    assert 5.3e9 < want < 5.5e9
