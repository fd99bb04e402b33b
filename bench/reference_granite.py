"""Plain float32 reference of a Granite training step: ``bench/reference.py``
with Granite's four scalar multipliers and a tied head.

Model, as ``transformers``' ``modeling_granite.py`` writes it, on the
decoder of ``bench/reference.py`` (same weights recipe, RoPE, masks and
blocked attention):

* ``x0 = embed(ids) * embedding_multiplier``;
* each sub-layer ``x <- x + residual_multiplier * branch(rmsnorm(x))``;
* attention scores ``q k^T * attention_multiplier`` (in place of
  ``1 / sqrt(head_dim)``);
* ``logits = rmsnorm(x_L) E^T / logits_scaling``, where the one
  (V_padded, D) table ``E`` is both the token gather and the transposed
  head, so its gradient is the sum of both uses.

Optimizer: as ``bench/reference.py``, except that the tied table takes the
head's branch: ``m = beta m + (1 - beta) g`` on the summed gradient, and
the step normalises ``m`` along the output (vocabulary) dimension, which
in the (V, D) storage is each row, over D.

The reference imports nothing of the program; all arithmetic is float32
with matrix products at ``HIGHEST`` (or the fp8 control of
``bench/reference.py``).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R
from bench.compare import named_norms
from bench.reference import F32, mm, rmsnorm, rope

MULTIPLIERS = ("embedding_multiplier", "residual_multiplier",
               "attention_multiplier", "logits_scaling")


@dataclasses.dataclass(frozen=True)
class Model(R.Model):
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    logits_scaling: float = 1.0

    @classmethod
    def from_config(cls, c: dict) -> "Model":
        if not c["tie_word_embeddings"]:
            raise ValueError("reference_granite runs the tied head only")
        base = R.Model.from_config(c)
        return cls(**dataclasses.asdict(base),
                   **{k: float(c[k]) for k in MULTIPLIERS})


def init_params(m: Model, key) -> dict:
    """``bench/reference.py``'s weights without the separate head (its key
    stays drawn, as the untied tree's)."""
    params = R.init_params(m, key)
    del params["lm_head"]
    return params


def layer(p: dict, x, pos, seg, m: Model, precision):
    """One Granite decoder layer on float32 parameters ``p``."""
    a = p["attn"]
    B, S, _ = x.shape
    h = rmsnorm(x, a["norm"], m.rms_eps)
    q = mm("bsd,de->bse", h, a["wq"], precision)
    k = mm("bsd,de->bse", h, a["wk"], precision)
    v = mm("bsd,de->bse", h, a["wv"], precision)
    if m.qkv_bias:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = rope(q.reshape(B, S, m.heads, m.head_dim), pos, m.rope_theta)
    k = rope(k.reshape(B, S, m.kv_heads, m.head_dim), pos, m.rope_theta)
    v = v.reshape(B, S, m.kv_heads, m.head_dim)
    # R.attention divides the scores by sqrt(head_dim); q scaled by
    # attention_multiplier * sqrt(head_dim) makes them q k^T * multiplier
    q = q * (m.attention_multiplier * math.sqrt(m.head_dim))
    o = R.attention(q, k, v, seg, m, precision)
    x = x + m.residual_multiplier * mm("bse,ed->bsd", o, a["wo"], precision)
    f = p["ffn"]
    h = rmsnorm(x, f["norm"], m.rms_eps)
    g = jax.nn.silu(mm("bsd,df->bsf", h, f["w_gate"], precision)) \
        * mm("bsd,df->bsf", h, f["w_up"], precision)
    return x + m.residual_multiplier * mm("bsf,fd->bsd", g, f["w_down"],
                                          precision)


class Reference(R.Reference):
    """Runs the Granite reference's first steps on the cell's devices."""

    def __init__(self, model: dict, optim: dict, devices, **kw):
        super().__init__(model, optim, devices, **kw)
        self.m = Model.from_config(model)

    def init(self, seed: int) -> dict:
        key = jax.random.PRNGKey(seed)
        if "init" not in self._jits:
            shapes = jax.eval_shape(lambda k: init_params(self.m, k), key)
            shard = R._map_paths(lambda p, s: self._spec(
                s.shape, p.startswith("layers")), shapes)
            self._jits["init"] = jax.jit(lambda k: init_params(self.m, k),
                                         out_shardings=shard)
        return self._jits["init"](key)

    def init_state(self, params: dict) -> dict:
        e = params["tok_embed"]["w"]
        vec = {p: x for p, x in R.leaf_paths(params)
               if not R.is_matrix(p.rsplit("/", 1)[-1])}

        def zeros(x):
            return jax.device_put(jnp.zeros(x.shape, F32), x.sharding)
        return {"mom": zeros(e), "mu": {p: zeros(x) for p, x in vec.items()},
                "nu": {p: zeros(x) for p, x in vec.items()}}

    # -- the jitted pieces that differ
    def _embed(self, e, tokens):
        return super()._embed(e, tokens) * self.m.embedding_multiplier

    def _fwd(self, stacked, l, x, pos, seg):
        return layer(self._layer_params(stacked, l), x, pos, seg, self.m,
                     self.precision)

    def _bwd(self, stacked, l, x, pos, seg, dy):
        p = self._layer_params(stacked, l)
        _, vjp = jax.vjp(lambda xx, pp: layer(pp, xx, pos, seg, self.m,
                                              self.precision), x, p)
        return vjp(dy)

    def _head(self, x, s, e, labels, weights):
        """The untied head's loss on ``E^T``, with ``1 / logits_scaling``
        carried by the final norm's scale (rmsnorm is linear in it): the
        gradients come back for ``s`` by the chain rule and for ``E`` in
        its (V, D) storage."""
        c = 1.0 / self.m.logits_scaling
        loss, (dx, ds, gw) = super()._head(x, s.astype(F32) * c, e.T,
                                           labels, weights)
        return loss, (dx, ds * c, gw.T)

    def _embed_grad(self, g_head, tokens, dx):
        """The table's gradient: the head's use plus the gather's."""
        return g_head.at[tokens.reshape(-1)].add(
            self.m.embedding_multiplier * dx.reshape(-1, dx.shape[-1]))

    def _head_step(self, e, mom, g, scale, lr):
        """Head momentum on the tied table, normalised over each row."""
        mom = self.o.beta * mom + (1 - self.o.beta) * scale * g
        ss = jnp.sum(mom * mom, axis=-1, keepdims=True)
        new = e.astype(F32) - lr * mom / (jnp.sqrt(ss) + self.o.norm_eps)
        return new.astype(e.dtype), mom

    # -- one step
    def step(self, params: dict, state: dict, batch: dict, count: int):
        """As ``bench/reference.py``'s step, with the tied table's gradient
        summed over its two uses before the clip and the head's update."""
        m = self.m
        lr = self.o.lr_at(count)
        b = {k: jax.device_put(np.asarray(v), self._rows(v.shape))
             for k, v in batch.items()}
        tok, pos, seg = b["tokens"], b["positions"], b["segment_ids"]
        lay = params["layers"]
        xs = [self._jit("embed", self._embed)(params["tok_embed"]["w"], tok)]
        fwd = self._jit("fwd", self._fwd)
        for l in range(m.layers):
            xs.append(fwd(lay, l, xs[-1], pos, seg))
        loss, (dx, g_s, g_e) = self._jit("head", self._head)(
            xs.pop(), params["final_norm"]["s"], params["tok_embed"]["w"],
            b["labels"], b["loss_weights"])
        vec = {"final_norm/s": g_s}
        sq = {}
        bwd = self._jit("bwd", self._bwd)
        col = self._jit("colnorm_layer", self._colnorm_layer,
                        donate_argnums=0)
        per_layer = []
        for l in reversed(range(m.layers)):
            dx, gp = bwd(lay, l, xs.pop(), pos, seg, dx)
            for sub in gp:
                for leaf in [n for n in gp[sub] if R.is_matrix(n)]:
                    lay[sub][leaf], s = col(lay[sub][leaf], l,
                                            gp[sub].pop(leaf), lr)
                    sq.setdefault(f"layers/{sub}/{leaf}", [None] * m.layers)
                    sq[f"layers/{sub}/{leaf}"][l] = s
            per_layer.append(gp)
        g_e = self._jit("embed_grad", self._embed_grad, donate_argnums=0)(
            g_e, tok, dx)
        sq["tok_embed/w"] = jnp.sum(g_e * g_e)
        per_layer.reverse()
        for sub in lay:
            for leaf in lay[sub]:
                if not R.is_matrix(leaf):
                    vec[f"layers/{sub}/{leaf}"] = jnp.stack(
                        [gp[sub][leaf] for gp in per_layer])
        for path, g in vec.items():
            sq[path] = jnp.sum(g * g, axis=-1) if path.startswith(
                "layers/") else jnp.sum(g * g)
        sq = {k: np.asarray(jnp.stack(v) if isinstance(v, list) else v,
                            np.float64) for k, v in sq.items()}
        gnorm = float(np.sqrt(sum(np.sum(v) for v in sq.values())))
        scale = min(1.0, self.o.clip_norm / (gnorm + 1e-9))
        grad_all = named_norms({k: np.sqrt(v) for k, v in sq.items()})
        kept = set(vec) | {"tok_embed/w"}
        got = {k: scale * v for k, v in grad_all.items()
               if k.split("[")[0] in kept}
        params["tok_embed"]["w"], state["mom"] = self._jit(
            "head_step", self._head_step, donate_argnums=(0, 1))(
                params["tok_embed"]["w"], state["mom"], g_e, scale, lr)
        adam = self._jit("adam", self._adam, donate_argnums=(0, 1, 2))
        for path, g in vec.items():
            node, leaf = R._node(params, path)
            node[leaf], state["mu"][path], state["nu"][path] = adam(
                node[leaf], state["mu"][path], state["nu"][path], g, scale,
                lr, count)
        return float(loss), got, grad_all
