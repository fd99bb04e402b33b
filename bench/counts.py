"""Operations and bytes a training step requires, from the configuration
and the batch alone, whatever implements them.

``model`` is a configuration file's dict (published key names). FLOPs:

* matrix products: 6 per matrix parameter per document token (forward 2,
  backward 4); the LM head counts its published vocabulary and the
  embedding, a gather, counts nothing;
* attention: 12 * heads * head_dim per (query, key) pair inside a document
  with the key at or before the query, per layer (forward 4, backward 8);
* the head's cross-entropy: 6 * hidden * vocab per labelled token.

Recomputation is never counted. Bytes of the SCALE update: each matrix is
read as a gradient and as a parameter and written once, all in the
parameter dtype, and the LM head's momentum is read and written once.
"""
from __future__ import annotations

import numpy as np

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def layer_matrix_params(m: dict) -> int:
    D, H, K = m["hidden_size"], m["num_attention_heads"], \
        m["num_key_value_heads"]
    hd, F = m["head_dim"], m["intermediate_size"]
    return D * H * hd * 2 + D * K * hd * 2 + 3 * D * F


def head_params(m: dict) -> int:
    return m["hidden_size"] * m["vocab_size"]


def matmul_params(m: dict) -> int:
    return m["num_hidden_layers"] * layer_matrix_params(m) + head_params(m)


def doc_tokens(batch: dict) -> int:
    return int(np.count_nonzero(np.asarray(batch["segment_ids"])))


def labelled_tokens(batch: dict) -> float:
    w = np.asarray(batch["loss_weights"])
    return float(np.sum(np.where(np.asarray(batch["labels"]) >= 0, w, 0.0)))


def causal_pairs(batch: dict) -> int:
    """(query, key) pairs with both in one document and key <= query."""
    seg = np.asarray(batch["segment_ids"])
    tot = 0
    for row in seg:
        _, n = np.unique(row[row > 0], return_counts=True)
        tot += int(np.sum(n.astype(np.int64) * (n + 1) // 2))
    return tot


def attention_flops(m: dict, batch: dict) -> float:
    return 12.0 * m["num_attention_heads"] * m["head_dim"] \
        * causal_pairs(batch) * m["num_hidden_layers"]


def xent_flops(m: dict, batch: dict) -> float:
    return 6.0 * m["hidden_size"] * m["vocab_size"] * labelled_tokens(batch)


def model_flops(m: dict, batch: dict) -> float:
    """Forward and backward FLOPs the step requires."""
    return 6.0 * matmul_params(m) * doc_tokens(batch) \
        + attention_flops(m, batch)


def update_bytes(m: dict, head_momentum_dtype: str = "float32") -> float:
    """Least bytes the SCALE update moves: gradient read, parameter read
    and write of every matrix, and the head momentum read and write."""
    p = DTYPE_BYTES[m["torch_dtype"]]
    embed = m["vocab_size"] * m["hidden_size"]
    mats = m["num_hidden_layers"] * layer_matrix_params(m) + embed \
        + head_params(m)
    return 3.0 * p * mats + 2.0 * DTYPE_BYTES[head_momentum_dtype] \
        * head_params(m)
