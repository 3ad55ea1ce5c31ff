"""Operations and bytes the algorithm needs, from shapes alone.

``paged_attention`` counts one decode query per row against the keys the
row attends (its length), not the padded page table the kernel walks: a
kernel that learns to skip padding raises its share honestly.  Model
FLOPs count 2 per multiply-add of every matrix product a token needs,
plus attention at the token's actual context.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

import numpy as np

__all__ = ["paged_attention_cost", "padded_attention_cost",
           "matmul_params", "model_flops"]


def paged_attention_cost(lengths: Iterable[int], *, heads: int,
                         kv_heads: int, head_dim: int, kv_itemsize: int,
                         q_itemsize: int) -> Tuple[float, float]:
    """(flops, bytes) of one decode query per row over ``lengths`` keys:
    q.k and p.v are 2 * heads * head_dim * n flops each; the row reads n
    keys and n values of kv_heads * head_dim, and reads q and writes the
    context once (rows of length 0 do nothing)."""
    n = np.asarray(list(lengths), np.float64)
    live = float((n > 0).sum())
    total = float(n.sum())
    flops = 4.0 * heads * head_dim * total
    bytes_ = (2.0 * kv_heads * head_dim * kv_itemsize * total
              + 2.0 * heads * head_dim * q_itemsize * live)
    return flops, bytes_


def padded_attention_cost(rows: int, table_pages: int, page_size: int,
                          **kw) -> Tuple[float, float]:
    """The same count over every row's whole padded page table: what the
    kernel walks, an upper bound of ``paged_attention_cost``."""
    return paged_attention_cost([table_pages * page_size] * rows, **kw)


def matmul_params(conf: Dict) -> Tuple[int, int]:
    """(matrix parameters of one layer, of the unembedding)."""
    d, h, kv = conf["hidden_size"], conf["num_attention_heads"], \
        conf["num_key_value_heads"]
    hd, ff = conf["head_dim"], conf["intermediate_size"]
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff
    return per_layer, d * conf["vocab_size"]


def model_flops(conf: Dict, prompts: Iterable[int],
                decode_contexts: Iterable[int]) -> float:
    """FLOPs of prefilling ``prompts`` (lengths; logits at the last
    position only) and of decoding one token at each of
    ``decode_contexts`` (keys attended, the new token's included)."""
    per_layer, unembed = matmul_params(conf)
    n_layers = conf["num_hidden_layers"]
    attn = 4.0 * conf["num_attention_heads"] * conf["head_dim"] * n_layers
    p = np.asarray(list(prompts), np.float64)
    c = np.asarray(list(decode_contexts), np.float64)
    flops = 2.0 * per_layer * n_layers * (p.sum() + c.size)
    flops += 2.0 * unembed * (p.size + c.size)
    flops += attn * (float((p * (p + 1) / 2).sum()) + float(c.sum()))
    return float(flops)
