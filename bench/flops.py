"""Operations and bytes the algorithm needs, from shapes alone.

``paged_attention`` counts one decode query per row against the keys the
row attends (its length), not the padded page table the kernel walks: a
kernel that learns to skip padding raises its share honestly.  Model
FLOPs are counted by the configuration's architecture
(``bench/arch/<name>.py``), which also holds the cost functions of any
kernel only that architecture runs.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

import numpy as np

from bench import arch

__all__ = ["paged_attention_cost", "padded_attention_cost", "model_flops"]


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


def model_flops(conf: Dict, prompts: Iterable[int],
                decode_contexts: Iterable[int]) -> float:
    """FLOPs of prefilling ``prompts`` and of decoding one token at each
    of ``decode_contexts``, as the configuration's architecture
    (``bench/arch/<reference>.py``) counts them."""
    return arch.of(conf).model_flops(conf, prompts, decode_contexts)
