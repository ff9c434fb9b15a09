"""Operations and bytes from shapes, against counts made by hand at small
shapes."""

import benchtree  # noqa: F401  (puts the repository on sys.path)
from chipbench.families import qwen2, tinybio

#: d 8, 2 heads of 4, 1 kv head, ff 16, 3 layers, vocab 10 (padded to 256)
SMALL = dict(hidden_size=8, num_attention_heads=2, num_key_value_heads=1,
             intermediate_size=16, num_hidden_layers=3, vocab_size=10,
             initializer_range=0.02, torch_dtype="bfloat16")


def test_qwen2_weights_and_matmuls():
    # per layer: q 8x8, o 8x8, k 8x4, v 8x4, gate/up/down 8x16 each
    per_layer = 64 + 64 + 32 + 32 + 3 * 128
    assert qwen2.matmul_params(SMALL) == 3 * per_layer + 10 * 8
    assert qwen2.matmul_params(SMALL, head=False) == 3 * per_layer
    # + biases 8+4+4 and two gains 8+8 per layer; embedding 256x8; ln_f 8
    elems = 3 * (per_layer + 16 + 16) + 256 * 8 + 8
    assert qwen2.weight_bytes(SMALL) == 2 * elems
    # 3 layers x (k, v) x 1 kv head x 4 x 2 bytes
    assert qwen2.kv_bytes_per_token(SMALL) == 48


def test_qwen2_step_counts():
    # attention over 5 cached positions: 3 layers x 2 products x 2 flops
    # x 2 heads x 4 dims x 5
    att5 = 3 * 2 * 2 * 2 * 4 * 5
    mm = 2 * qwen2.matmul_params(SMALL)
    assert qwen2.decode_flops(SMALL, [5]) == mm + att5
    assert qwen2.decode_flops(SMALL, [5, 5]) == 2 * (mm + att5)
    assert qwen2.decode_bytes(SMALL, [5, 7]) == (
        qwen2.weight_bytes(SMALL) + 48 * (4 + 6) + 48 * 2)
    # causal prefill of 4 tokens: 10 query-key pairs
    att = 3 * 2 * 2 * 2 * 4 * 10
    assert qwen2.prefill_flops(SMALL, 4) == (
        2 * qwen2.matmul_params(SMALL, head=False) * 4 + att + 2 * 10 * 8)
    flops, moved = qwen2.flash_cost(SMALL, 4)
    assert flops == att / 3
    # q and out (4 x 2 heads x 4) and k, v (4 x 1 x 4), in bf16
    assert moved == 2 * (2 * 32 + 2 * 16)


def test_tinybio_kernels():
    cfg = dict(n=1024, taps=8, win=16, n_windows=4, n_sv=5, n_features=6)
    c = tinybio.kernel_costs(cfg)
    assert c["fir"] == (2 * 1024 * 8, (2 * 1024 + 8) * 4)
    assert c["delineate"] == (11 * 1024, 1024 * 5)
    # 4 transforms of 16 points: 4 stages of 8 butterflies of 10 flops
    assert c["stockham_fft"] == (4 * 4 * 8 * 10, 4 * 16 * 16)
    assert c["svm"] == (2 * 4 * 5 * 6 + 6 * 4 * 5, (24 + 35 + 4) * 4)
    assert tinybio.pipeline_flops(cfg) == sum(f for f, _ in c.values())
