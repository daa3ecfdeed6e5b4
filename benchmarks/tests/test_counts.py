"""The FLOP and byte functions against numbers worked by hand: the
kernels' from ``harness.counts``, the model's through its family."""

from harness import counts, loader, peaks

MISTRAL = {"hidden_size": 4096, "intermediate_size": 14336,
           "num_attention_heads": 32, "num_key_value_heads": 8,
           "head_dim": 128, "num_hidden_layers": 24, "vocab_size": 32768}


def test_flash_forward_one_shape():
    # 4 sequences of 2048, 16 heads of 128: pairs = 2048*2049/2 = 2,098,176;
    # 4 flops x 128 per pair and head: 4*16*... = 68,753,031,168
    assert counts.flash_causal_flops(4, 2048, 16, 128) == 4 * 4 * 16 * 128 * 2098176
    assert counts.flash_causal_flops(4, 2048, 16, 128) == 68753031168
    assert counts.flash_causal_flops(4, 2048, 16, 128, backward=True) == 2.5 * 68753031168
    # Q and O: 4*2048*16*128*2 B = 33,554,432 each; K, V the same at 16 kv heads
    assert counts.flash_bytes(4, 2048, 16, 16, 128) == 4 * 33554432
    need, bound = counts.roofline_seconds(
        68753031168, 4 * 33554432, peaks.peaks("TPU v5 lite"))
    assert bound == "compute" and abs(need - 68753031168 / 197e12) < 1e-12


def test_decode_attention_one_shape():
    # 16 slots at 600 visible rows each = 9,600 rows, 8 kv heads of 128, bf16:
    # K and V: 2 * 9600 * 8 * 128 * 2 B = 39,321,600 B a layer
    assert counts.decode_attention_bytes(9600, 8, 128) == 39321600
    assert counts.decode_attention_flops(9600, 32, 128) == 4 * 9600 * 32 * 128
    need, bound = counts.roofline_seconds(
        counts.decode_attention_flops(9600, 32, 128), 39321600,
        peaks.peaks("TPU v5 lite"))
    assert bound == "memory" and abs(need - 39321600 / 819e9) < 1e-12


def test_model_counts_of_the_llama_family():
    llama = loader.load_family("llama").counts
    # per layer: 4096*4096*2 + 2*4096*1024 + 3*4096*14336 = 218,103,808
    assert llama.matmul_params(MISTRAL) == 24 * 218103808 + 32768 * 4096
    assert llama.total_params(MISTRAL) == (
        24 * 218103808 + 2 * 32768 * 4096 + 49 * 4096)
    # one prompt of 3 tokens, one decoded token over 4 rows
    n = llama.matmul_params(MISTRAL)
    assert llama.serve_flops(MISTRAL, [3], [4]) == 2.0 * n * 4 + 4.0 * 4096 * 24 * (6 + 4)
    assert llama.train_flops_per_token(MISTRAL, 2048) == 6.0 * n + 6.0 * 24 * 2048 * 4096
