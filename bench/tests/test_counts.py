"""FLOP and byte counters against hand counts on a tiny configuration."""
import numpy as np
import pytest

from bench import counts

MODEL = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
         "head_dim": 4, "intermediate_size": 16, "vocab_size": 10,
         "num_hidden_layers": 3, "torch_dtype": "bfloat16"}


def batch():
    # row 0: documents of 3 and 2 tokens, then one pad; row 1: one of 6
    seg = np.array([[1, 1, 1, 2, 2, 0], [1, 1, 1, 1, 1, 1]], np.int32)
    w = np.array([[1, 1, 0, 1, 0, 0], [1, 1, 1, 1, 1, 0]], np.float32)
    labels = np.where(w > 0, 3, -1).astype(np.int32)
    return {"segment_ids": seg, "loss_weights": w, "labels": labels}


def test_params():
    # q and o: 8*2*4 each; k and v: 8*1*4 each; MLP 3*8*16
    assert counts.layer_matrix_params(MODEL) == 64 + 64 + 32 + 32 + 384
    assert counts.head_params(MODEL) == 80
    assert counts.matmul_params(MODEL) == 3 * 576 + 80


def test_tokens_and_pairs():
    b = batch()
    assert counts.doc_tokens(b) == 11
    assert counts.labelled_tokens(b) == 8.0
    # 3*4/2 + 2*3/2 + 6*7/2
    assert counts.causal_pairs(b) == 6 + 3 + 21


def test_flops():
    b = batch()
    assert counts.attention_flops(MODEL, b) == 12 * 2 * 4 * 30 * 3
    assert counts.xent_flops(MODEL, b) == 6 * 8 * 10 * 8
    assert counts.model_flops(MODEL, b) == pytest.approx(
        6 * (3 * 576 + 80) * 11 + 12 * 2 * 4 * 30 * 3)


def test_update_bytes():
    # bf16 matrices: 3 layers' 576 + embedding 80 + head 80, read g, read p,
    # write p; f32 head momentum read and written
    assert counts.update_bytes(MODEL) == 3 * 2 * (3 * 576 + 160) + 2 * 4 * 80
