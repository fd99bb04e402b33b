"""granite-3-8b — dense GQA with Granite's scalar multipliers and a tied head.

Source: ibm-granite/granite-3.0-8b-base ``config.json``
(https://huggingface.co/ibm-granite/granite-3.0-8b-base, ``model_type``
granite) and the layer equations of ``transformers``' ``modeling_granite.py``:

* ``x0 = embed(ids) * embedding_multiplier`` (12.0);
* each sub-layer ``x <- x + residual_multiplier * branch(rmsnorm(x))`` (0.22);
* attention scores ``q k^T * attention_multiplier`` (0.0078125 = 1/128, in
  place of 1/sqrt(head_dim));
* ``logits = rmsnorm(x_L) E^T / logits_scaling`` (16.0), with the head tied
  to the token embedding E (``tie_word_embeddings``): 40 layers of 199.23 M
  and one 49155 x 4096 table make the model card's 8.17 B parameters.

vocab 49155 is not 128-aligned; the embedding pads to 49280 rows and the
loss masks padded logits (production vocab-padding, Megatron-style).
"""
from repro.models import ModelConfig

MULTIPLIERS = dict(embedding_multiplier=12.0, residual_multiplier=0.22,
                   attention_multiplier=0.0078125, logits_scaling=16.0)

CONFIG = ModelConfig(
    name="granite-3-8b", family="dense",
    n_layers=40, d_model=4096, n_heads=32, n_kv_heads=8, head_dim=128,
    d_ff=12800, vocab_size=49155, tie_embeddings=True, **MULTIPLIERS,
)

SMOKE = ModelConfig(
    name="granite-3-8b-smoke", family="dense",
    n_layers=2, d_model=128, n_heads=8, n_kv_heads=2, head_dim=16,
    d_ff=256, vocab_size=387,  # deliberately unaligned -> exercises padding
    dtype="float32", attn_kv_block=32, attn_q_block=32, loss_chunk=32,
    tie_embeddings=True, **MULTIPLIERS,
)
