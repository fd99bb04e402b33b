"""Share of the attention kernels' grid steps that copy no block: steps
whose streamed block (k/v in ``attn_fwd`` and ``attn_bwd_dq``, q/dout in
``attn_bwd_dkv``) has the index of the step before. The program's own
index maps count them (``fetch_repeats``) for the traced steps' batches,
at the tiles the kernels pick for the configuration; each kernel weighs as
often as the trace ran it. None on a program without a fetch plan."""
import numpy as np

from bench import trace as tr

KERNELS = {"attn_fwd": "fwd", "attn_bwd_dq": "dq", "attn_bwd_dkv": "dkv"}


def read(run):
    if run.trace is None:
        return None
    try:
        from repro.kernels.attention.attention import fetch_repeats
    except ImportError:
        return None
    import jax
    import jax.numpy as jnp
    n = tr.program_runs(run.trace, run.program)
    ops = tr.leaves(run.trace.ops[min(run.trace.ops)])
    calls = {k: sum(tr.op_kind(t) == name for *_, t in ops)
             for name, k in KERNELS.items()}
    if not n or not any(calls.values()):
        return None
    m = run.model
    dtype = jnp.dtype(m["torch_dtype"])
    repeats = steps = 0
    for batch in run.batches[:n]:
        seg = np.asarray(batch["segment_ids"])
        B, S = seg.shape
        q = jax.ShapeDtypeStruct(
            (B, S, m["num_attention_heads"], m["head_dim"]), dtype)
        kv = jax.ShapeDtypeStruct(
            (B, S, m["num_key_value_heads"], m["head_dim"]), dtype)
        got = fetch_repeats(q, kv, kv, causal=True, segments=(seg, seg))
        for k, c in calls.items():
            repeats += c * got[k][0]
            steps += c * got[k][1]
    return 100.0 * repeats / steps
