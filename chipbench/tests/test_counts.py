"""The benchmark's FLOP and byte counts against values worked by hand."""

from chipbench import counts

# a smoke-sized decoder: 2 layers, d_model 128, 4 heads of 32 over 2 KV
# heads, d_ff 512, vocab 512, GELU MLP, untied head
SMOKE = {
    "n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 2, "d_ff": 512,
    "vocab": 512, "mlp": "gelu_tanh", "tie_embeddings": False,
}


def test_matmul_params_by_hand():
    # attention: q 128x128 + k,v 2 x 128x64 + o 128x128 = 49,152
    # MLP: up 128x512 + down 512x128 = 131,072; per layer 180,224
    # two layers 360,448, plus the head 128x512 = 65,536
    assert counts.matmul_params(SMOKE) == 425_984


def test_swiglu_counts_three_matrices():
    swiglu = dict(SMOKE, mlp="swiglu")
    assert counts.matmul_params(swiglu) == 425_984 + 2 * 128 * 512


def test_tied_head_counted_once():
    assert counts.matmul_params(dict(SMOKE, tie_embeddings=True)) == 425_984


def test_train_flops_per_token_by_hand():
    # 6 x 425,984 = 2,555,904; attention 12 x 2 layers x 4 heads x 32 x 64 = 196,608
    assert counts.train_flops_per_token(SMOKE, seq=64) == 2_752_512


def test_paper_transformer_flops_per_token():
    paper = {
        "n_layers": 6, "d_model": 512, "n_heads": 8, "n_kv_heads": 8, "d_ff": 2048,
        "vocab": 37000, "mlp": "gelu_tanh",
    }
    n = 6 * (4 * 512 * 512 + 2 * 512 * 2048) + 512 * 37000
    assert counts.matmul_params(paper) == n == 37_818_368
    assert counts.train_flops_per_token(paper, 128) == 6 * n + 12 * 6 * 8 * 64 * 128


def test_reduce_min_bytes_by_hand():
    tensors = [
        (2048, 1, 4, 4),  # one fp32 learner: read g, read m, write m, write g_hat
        (512, 1, 4, 4),  # under min_size: averaged densely, not counted
        (4096, 2, 4, 2),  # two learners, bf16 residues: 2 x (4 + 2 x 2) + 4 = 20 B
    ]
    assert counts.reduce_min_bytes(tensors, min_size=1024) == 2048 * 16 + 4096 * 20
