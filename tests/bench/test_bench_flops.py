"""Operations and bytes of the paged-attention kernel and of the model,
against hand-worked values for one small shape."""
import numpy as np
import pytest

from bench import flops
from bench.arch import qwen3

SMALL = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
         "head_dim": 4, "intermediate_size": 16, "vocab_size": 10,
         "num_hidden_layers": 3}


def test_paged_attention_counts_attended_keys():
    # rows attend 5, 0 and 3 keys; 2 heads of 4, 1 KV head, f32 pages
    f, b = flops.paged_attention_cost([5, 0, 3], heads=2, kv_heads=1,
                                      head_dim=4, kv_itemsize=4,
                                      q_itemsize=4)
    # q.k and p.v: 2 flops x heads x head_dim per key, twice
    assert f == 4 * 2 * 4 * 8
    # keys + values: kv_heads x head_dim x 4 B per key, each; q in and
    # context out for the 2 live rows
    assert b == 2 * 1 * 4 * 4 * 8 + 2 * 2 * 4 * 4 * 2


def test_attended_never_exceeds_the_padded_table():
    rng = np.random.default_rng(0)
    kw = dict(heads=10, kv_heads=2, head_dim=128, kv_itemsize=4,
              q_itemsize=4)
    for _ in range(50):
        rows, pages, page = 32, 132, 64
        lengths = rng.integers(0, pages * page + 1, rows)
        f, b = flops.paged_attention_cost(lengths, **kw)
        fp, bp = flops.padded_attention_cost(rows, pages, page, **kw)
        assert f <= fp and b <= bp


def test_matmul_params_of_a_qwen3_layer():
    per_layer, unembed = qwen3.matmul_params(SMALL)
    # q 8x2x4 + k,v 8x1x4 each + o 2x4x8 + gate,up,down 8x16 each
    assert per_layer == 64 + 32 + 32 + 64 + 3 * 128
    assert unembed == 80


def test_model_flops_hand_worked():
    per_layer, unembed = qwen3.matmul_params(SMALL)
    # one prompt of 3 tokens (logits at its last position only) and two
    # decode tokens attending 4 and 5 keys
    got = qwen3.model_flops(SMALL, [3], [4, 5])
    mm = 2 * per_layer * 3 * (3 + 2) + 2 * unembed * (1 + 2)
    attn = 4 * 2 * 4 * 3 * ((1 + 2 + 3) + (4 + 5))
    assert got == pytest.approx(mm + attn)


def test_model_flops_is_the_architectures():
    # found by the configuration's ``reference`` name
    conf = dict(SMALL, name="small", reference="qwen3")
    assert flops.model_flops(conf, [3], [4, 5]) \
        == qwen3.model_flops(SMALL, [3], [4, 5])
    with pytest.raises(ValueError, match="known: .*qwen3"):
        flops.model_flops(dict(conf, reference="nosuch"), [3], [4])
