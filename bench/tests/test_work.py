"""The benchmark's operation and byte counts against counts worked out by hand."""
import pytest

from bench import work
from bench.models import dense

#: hidden 4, 2 query heads of width 2 over 1 KV head, MLP 6, one layer, vocabulary 10
TINY = {"hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads": 1,
        "intermediate_size": 6, "num_hidden_layers": 1, "vocab_size": 10, "qkv_bias": True}


def test_matmul_params_by_hand():
    # q,k,v 4*(2+1+1)*2 = 32; o 2*2*4 = 16; mlp 3*4*6 = 72; head 4*10 = 40
    assert dense.matmul_params(TINY) == 160


def test_qwen25_3b_weights_match_the_published_count():
    q = {"hidden_size": 2048, "num_attention_heads": 16, "num_key_value_heads": 2,
         "intermediate_size": 11008, "num_hidden_layers": 36, "vocab_size": 151936,
         "qkv_bias": True}
    # 36 * (2048*20*128 + 2048*2048 + 3*2048*11008) + 2048*151936
    assert dense.matmul_params(q) == 3_085_697_024


def test_paged_attention_call_by_hand():
    flops, nbytes = dense.paged_attention_call(TINY, [3, 0])
    assert flops == 4 * 2 * 2 * 3  # q.k and p.v over 3 tokens, 2 heads of width 2
    # k and v of 3 tokens (2*1*2*3 = 12 values), q and o of both rows (2*2*2*2 = 16)
    assert nbytes == (12 + 16) * 2


def test_decode_step_counts_live_rows_only():
    assert dense.decode_step_flops(TINY, [3, 0]) == 2 * 160 * 1 + 48
    assert dense.decode_step_flops(TINY, [0, 0]) == 0


def test_roofline_names_its_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_s(50.0, 10.0, peak) == (1.0, "memory")
    assert work.roofline_s(500.0, 1.0, peak) == (5.0, "compute")


def test_unknown_device_is_an_error():
    assert work.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
