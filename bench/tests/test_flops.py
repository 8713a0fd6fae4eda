"""Each family's FLOP count against a count by hand at one shape."""

from reference import dense
from tiny import DENSE


def test_dense_flops_by_hand():
    # d 64, 4 q heads / 2 kv heads of 16, d_ff 128, vocab 256, 2 layers
    # per token per layer: q 64x64, k 64x32, v 64x32, o 64x64,
    # gate/up 64x128 each, down 128x64 -> 36,864 weights, 73,728 FLOPs
    # prompt 5, 3 new -> 7 tokens through the layers; causal contexts
    # 1..7 sum to 28; attention 4 * 4 heads * 16 * 28 = 7,168 per layer
    # head: 2 * 64 * 256 per generated token
    want = 2 * (73_728 * 7 + 7_168) + 3 * 2 * 64 * 256
    assert dense.request_flops(DENSE, 5, 3) == want


def test_param_counts_by_hand():
    # dense layer: 2 norms 128, attention 12,288, qk-norm 32, mlp 24,576
    assert dense.param_count(DENSE) == 2 * 256 * 64 + 64 + 2 * 37_024
