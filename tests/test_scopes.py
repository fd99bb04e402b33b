"""Named scopes of the compiled train step.

The device trace charges each operation to the ``op_name`` of its HLO
instruction, and the benchmark's per-layer readers
(``bench/metrics/{mlp,remat,embed}_share.py``) key on the scopes the model
and trainer open. These tests compile the smoke train step (SCALE, guard
on, ``remat="full"``) of one architecture per mechanism family and check
that every matmul is charged to a layer, that the recomputed forward keeps
its layer's name, and that the scopes the readers read occur.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch
from repro.core import linear_warmup_cosine, make_optimizer
from repro.data import make_dataset
from repro.models import init_params
from repro.training import GuardPolicy, init_state, make_train_step

ARCHS = ["qwen2-7b",               # dense GQA + MLP
         "deepseek-v3-671b",       # MLA + MoE
         "jamba-1.5-large-398b"]   # mamba + attention + MoE
SUBLAYERS = ("attn", "cross", "mlp", "moe", "mamba")
SCOPES = ("embed",) + SUBLAYERS + ("head", "clip", "step_metrics",
                                   "optimizer_update", "guard", "obs_stats")
_INSTR = re.compile(r"^\s*(?:ROOT )?%\S+ = \S+ ([\w-]+)\(.*"
                    r"op_name=\"(jit\(train_step\)/[^\"]*)\"", re.M)


@functools.lru_cache(maxsize=None)
def op_names(arch: str) -> tuple:
    """((opcode, op_name), ...) of the compiled smoke train step."""
    cfg = get_arch(arch, smoke=True)
    seq, batch = 32, 2
    cfg.attn_kv_block = cfg.attn_q_block = min(cfg.attn_kv_block, seq // 2)
    cfg.loss_chunk = min(cfg.loss_chunk, seq)
    assert cfg.remat == "full"
    tx = make_optimizer("scale", linear_warmup_cosine(1e-3, 10))
    step = make_train_step(cfg, tx, clip_norm=1.0, guard=GuardPolicy(),
                           donate=True)
    state = init_state(init_params(jax.random.PRNGKey(0), cfg), tx,
                       guard=True)
    data = make_dataset(cfg, seq_len=seq, global_batch=batch, seed=0)
    host = {k: jnp.asarray(v) for k, v in data.host_batch_at(0).items()}
    text = step.lower(state, host).compile().as_text()
    return tuple(_INSTR.findall(text))


def _has(name: str, scopes) -> bool:
    return any(f"/{s}/" in name for s in scopes)


@pytest.mark.parametrize("arch", ARCHS)
def test_every_matmul_is_charged_to_a_layer(arch):
    mats = [n for code, n in op_names(arch)
            if code in ("dot", "convolution")]
    assert mats
    unnamed = sorted({n for n in mats if not _has(n, SCOPES)})
    assert not unnamed, unnamed


@pytest.mark.parametrize("arch", ARCHS)
def test_recomputed_forward_keeps_its_layer_scope(arch):
    remat = [n for _, n in op_names(arch) if "/rematted_computation/" in n]
    assert remat, "remat='full' left no recomputed forward"
    bad = sorted({n for n in remat if "/layers/" not in n
                  or not _has(n.split("/rematted_computation", 1)[1],
                              SUBLAYERS)})
    assert not bad, bad


@pytest.mark.parametrize("arch", ARCHS)
def test_reader_scopes_occur(arch):
    names = [n for _, n in op_names(arch)]
    assert any(_has(n, ("mlp", "moe")) for n in names)
    assert any("/transpose(jvp(fwd))/embed/" in n for n in names), \
        "the embedding's gradient is not under embed"
    assert any("/head/" in n for n in names)
    assert any("/final_norm/" in n for n in names)
