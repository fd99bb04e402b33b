"""Serving example: batched prefill + greedy decode with a persistent KV
cache, through the serving-step factories.

  PYTHONPATH=src python examples/serve.py --arch qwen2-7b
(uses the reduced smoke config on CPU; on a TPU slice drop --smoke logic
and point --arch at the full config.)
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.models import init_params
from repro.training import greedy_generate, make_decode_step, make_prefill_step

ap = argparse.ArgumentParser()
ap.add_argument("--arch", default="qwen2-7b")
ap.add_argument("--batch", type=int, default=4)
ap.add_argument("--prompt-len", type=int, default=16)
ap.add_argument("--gen-len", type=int, default=16)
args = ap.parse_args()

cfg = get_arch(args.arch, smoke=True)
params = init_params(jax.random.PRNGKey(0), cfg)
max_seq = args.prompt_len + args.gen_len

prompt = jax.random.randint(jax.random.PRNGKey(1),
                            (args.batch, args.prompt_len), 0, cfg.vocab_size)

prefill = jax.jit(make_prefill_step(cfg, max_seq=max_seq))
decode = jax.jit(make_decode_step(cfg))

t0 = time.time()
state, logits = prefill(params, prompt)
jax.block_until_ready(logits)
print(f"prefill: batch={args.batch} len={args.prompt_len} "
      f"({time.time()-t0:.2f}s incl. compile)")

tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
out = [tok]
t0 = time.time()
for i in range(args.gen_len - 1):
    state, logits = decode(params, state, tok)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    out.append(tok)
jax.block_until_ready(tok)
dt = time.time() - t0
gen = jnp.concatenate(out, 1)
print(f"decode: {args.gen_len-1} steps in {dt:.2f}s "
      f"({args.batch*(args.gen_len-1)/dt:.1f} tok/s)")
print("generated ids[0]:", list(map(int, gen[0])))

# one-call variant
gen2 = greedy_generate(cfg, params, prompt, n_steps=args.gen_len,
                       max_seq=max_seq)
assert (gen2 == gen).all(), "generate mismatch"
print("greedy_generate matches step-by-step decode")
