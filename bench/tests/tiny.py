"""A configuration small enough for the CPU, in the benchmark's format."""
import copy

# a mix of short documents and documents longer than a row; lengths are
# given in tokens (one token a KiB)
CORPUS = {"tokens_per_byte": 1 / 1024,
          "components": [["short", 70.0, 16.0], ["long", 30.0, 200.0]]}
TRAFFIC = {"corpus": CORPUS, "row_len": 64, "ring": 4, "layout_seed": 0,
           "bigram_prob": 0.8, "zipf_a": 1.2}

_CONFIG = {
    "registry": "qwen2-7b", "reference": "reference", "chips": 1,
    "tokens_per_step": 256,
    "model": {"hidden_size": 64, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 16,
              "intermediate_size": 128, "vocab_size": 250,
              "vocab_pad_multiple": 128, "num_hidden_layers": 2,
              "attention_bias": True, "rope_theta": 1e6,
              "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
              "initializer_range": 0.02, "torch_dtype": "float32"},
    "optimizer": {"name": "scale_fused", "lr": 0.01, "schedule_steps": 10,
                  "warmup_frac": 0.1, "final_frac": 0.1, "clip_norm": 1.0,
                  "beta": 0.9, "adam_b1": 0.9, "adam_b2": 0.999,
                  "adam_eps": 1e-8, "norm_eps": 1e-8}}


def config(dtype: str = "float32", chips: int = 1) -> dict:
    c = copy.deepcopy(_CONFIG)
    c["model"]["torch_dtype"] = dtype
    c["chips"] = chips
    return c
