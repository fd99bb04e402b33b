"""Granite at the program's SMOKE widths (``configs/granite_3_8b.py``),
in the benchmark's format: the four multipliers, the tied head and the
unaligned vocabulary of 387 rows (padded to 512)."""
import copy

from bench.tests import tiny

MODEL = {"hidden_size": 128, "num_attention_heads": 8,
         "num_key_value_heads": 2, "head_dim": 16,
         "intermediate_size": 256, "vocab_size": 387,
         "attention_bias": False, "rope_theta": 1e4, "rms_norm_eps": 1e-5,
         "tie_word_embeddings": True, "embedding_multiplier": 12.0,
         "residual_multiplier": 0.22, "attention_multiplier": 0.0078125,
         "logits_scaling": 16.0}
TRAFFIC = tiny.TRAFFIC


def config(chips: int = 1) -> dict:
    c = tiny.config("float32", chips)
    c["registry"], c["reference"] = "granite-3-8b", "reference_granite"
    c["model"].update(copy.deepcopy(MODEL))
    return c
