"""Plain float32 reference of a training step: dense GQA decoder + SCALE.

It follows the configuration file (``bench/configs/<config>.json``) and
the published description, and imports nothing of the program. It makes
its own weights from the seed, by the same recipe as the configuration's
initialisation (normal(0, initializer_range) in float32, stored in the
parameter dtype, norm scales 1, biases 0, one key per leaf), and trains on
the very batches the program trained on.

Model: token embedding; per layer pre-RMSNorm GQA self-attention (QKV
bias where the configuration has it, rotate-half RoPE on within-document
positions, causal and within-document mask from the segment ids) and
pre-RMSNorm SwiGLU MLP, each added to the residual; final RMSNorm; LM head
over the padded vocabulary with the padding columns masked out; mean
cross-entropy over the loss-weighted tokens, its gradient written out as
softmax minus one-hot.

Optimizer, as the configuration states it: the global gradient norm is
clipped to ``clip_norm``; matrices step by ``-lr * g / (||g_col|| + eps)``
(column norm over the input axis, per layer), the LM head by the same on
its momentum ``m = beta m + (1 - beta) g``, and vectors (norm scales,
biases) by Adam; the learning rate follows linear warm-up then cosine.
Parameters are stored in the configuration's dtype after every step; all
arithmetic is float32 with matrix products at ``HIGHEST`` precision.

Memory: the step never holds a float32 copy of the whole model. Layers run
one at a time from their saved inputs, each layer's gradient updates its
matrices as soon as it is known, and the head's loss runs in blocks of
tokens. Matrices other than the head apply their update before the clip
factor is known; column normalisation cancels that factor except against
``eps`` (1e-8 next to column norms of 1e-5 and more), which this order
leaves out.

``precision="fp8"`` is the control: every matrix product rounds its
operands to float8 e4m3 and its incoming gradient to e5m2, each with one
scale per tensor, as fp8 training does.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench.compare import named_norms

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Model:
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    vocab_padded: int
    layers: int
    qkv_bias: bool
    rope_theta: float
    rms_eps: float
    init_std: float
    dtype: str

    @classmethod
    def from_config(cls, c: dict) -> "Model":
        m = int(c["vocab_pad_multiple"])
        v = int(c["vocab_size"])
        return cls(d=int(c["hidden_size"]),
                   heads=int(c["num_attention_heads"]),
                   kv_heads=int(c["num_key_value_heads"]),
                   head_dim=int(c["head_dim"]),
                   ff=int(c["intermediate_size"]),
                   vocab=v, vocab_padded=(v + m - 1) // m * m,
                   layers=int(c["num_hidden_layers"]),
                   qkv_bias=bool(c["attention_bias"]),
                   rope_theta=float(c["rope_theta"]),
                   rms_eps=float(c["rms_norm_eps"]),
                   init_std=float(c["initializer_range"]),
                   dtype=c["torch_dtype"])

    @property
    def jdtype(self):
        return {"bfloat16": jnp.bfloat16, "float32": F32}[self.dtype]


@dataclasses.dataclass(frozen=True)
class Optim:
    lr: float
    schedule_steps: int
    warmup_frac: float
    final_frac: float
    clip_norm: float
    beta: float
    adam_b1: float
    adam_b2: float
    adam_eps: float
    norm_eps: float

    @classmethod
    def from_config(cls, c: dict) -> "Optim":
        return cls(**{f.name: c[f.name] for f in dataclasses.fields(cls)})

    def lr_at(self, count: int) -> float:
        warm = max(1, int(self.schedule_steps * self.warmup_frac))
        if count < warm:
            return self.lr * count / warm
        prog = min(max((count - warm) / max(1, self.schedule_steps - warm),
                       0.0), 1.0)
        return self.lr * (self.final_frac + (1 - self.final_frac) * 0.5
                          * (1 + math.cos(math.pi * prog)))


# ------------------------------------------------------------------ layout

def layer_shapes(m: Model) -> dict:
    """{sub: {leaf: (shape, init)}} of one layer, in the config's names."""
    D, H, K, hd, F = m.d, m.heads, m.kv_heads, m.head_dim, m.ff
    attn = {"norm": ((D,), "ones"), "wq": ((D, H * hd), "normal"),
            "wk": ((D, K * hd), "normal"), "wv": ((D, K * hd), "normal"),
            "wo": ((H * hd, D), "normal")}
    if m.qkv_bias:
        attn.update(bq=((H * hd,), "zeros"), bk=((K * hd,), "zeros"),
                    bv=((K * hd,), "zeros"))
    ffn = {"norm": ((D,), "ones"), "w_gate": ((D, F), "normal"),
           "w_up": ((D, F), "normal"), "w_down": ((F, D), "normal")}
    return {"attn": attn, "ffn": ffn}


def is_matrix(leaf: str) -> bool:
    """Matrices are named ``w*``; norm scales and biases are vectors."""
    return leaf.startswith("w")


def init_params(m: Model, key) -> dict:
    """Weights from ``PRNGKey(seed)``: one key per leaf, split in
    sorted-name order within each block, one key per layer."""
    dt = m.jdtype

    def one(key, shape, init):
        if init == "ones":
            return jnp.ones(shape, dt)
        if init == "zeros":
            return jnp.zeros(shape, dt)
        return (m.init_std * jax.random.normal(key, shape, F32)).astype(dt)

    def block(key, spec):
        names = [(s, n) for s in sorted(spec) for n in sorted(spec[s])]
        ks = jax.random.split(key, len(names))
        out = {s: {} for s in spec}
        for k, (s, n) in zip(ks, names):
            out[s][n] = one(k, *spec[s][n])
        return out

    keys = jax.random.split(key, 4)
    V, D = m.vocab_padded, m.d
    layers = jax.vmap(lambda k: block(k, layer_shapes(m)))(
        jax.random.split(keys[3], m.layers))
    return {"tok_embed": {"w": one(jax.random.split(keys[0], 1)[0], (V, D),
                                   "normal")},
            "final_norm": {"s": jnp.ones((D,), dt)},
            "lm_head": {"w": one(jax.random.split(keys[2], 1)[0], (D, V),
                                 "normal")},
            "layers": layers}


def leaf_paths(tree: dict, prefix: str = "") -> list:
    out = []
    for k in sorted(tree):
        v = tree[k]
        p = f"{prefix}/{k}" if prefix else k
        out += leaf_paths(v, p) if isinstance(v, dict) else [(p, v)]
    return out


# -------------------------------------------------------------- fp8 control

def _einsum(spec, a, b, precision=HIGHEST):
    return jnp.einsum(spec, a, b, precision=precision,
                      preferred_element_type=F32)


def _fp8(x, dtype):
    """(values of ``x / s`` rounded to ``dtype``, as float32; scale s)."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / s).astype(dtype).astype(F32), s


@functools.lru_cache(maxsize=None)
def _fp8_einsum(spec):
    # fp8 values are exact in bfloat16, so one bfloat16 pass with float32
    # accumulation multiplies them exactly; the scales apply afterwards
    one_pass = functools.partial(_einsum, spec,
                                 precision=jax.lax.Precision.DEFAULT)

    @jax.custom_vjp
    def f(a, b):
        return fwd(a, b)[0]

    def fwd(a, b):
        (qa, sa), (qb, sb) = _fp8(a, jnp.float8_e4m3fn), \
            _fp8(b, jnp.float8_e4m3fn)
        return one_pass(qa, qb) * (sa * sb), (qa, sa, qb, sb)

    def bwd(res, dy):
        qa, sa, qb, sb = res
        qd, sd = _fp8(dy, jnp.float8_e5m2)
        _, vjp = jax.vjp(one_pass, qa, qb)
        da, db = vjp(qd)
        return da * (sd * sb), db * (sd * sa)

    f.defvjp(fwd, bwd)
    return f


def mm(spec, a, b, precision):
    if precision == "fp8":
        return _fp8_einsum(spec)(a, b)
    return _einsum(spec, a, b)


# ------------------------------------------------------------------ model

def rmsnorm(x, s, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * s


def rope(x, pos, theta):
    """Rotate-half RoPE; x (B, S, n, hd), pos (B, S)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos.astype(F32)[..., None] * inv
    c, s = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def attention(q, k, v, seg, m: Model, precision, q_block: int = 512,
              parts: int = 4):
    """Causal attention within documents.

    q (B, S, H, hd); k, v (B, S, K, hd); seg (B, S), 0 on pad. The row is
    cut into ``parts`` spans of queries, and the queries of span j see
    only the keys of spans 0..j, since every later key is masked; inside a
    span, blocks of ``q_block`` queries see those keys under the mask. A
    query with no key left (a pad token) gets weights that feed nothing.
    """
    S = q.shape[1]
    if S % (parts * q_block):
        parts = 1
    n = S // parts
    return jnp.concatenate(
        [_attend(q[:, j * n:(j + 1) * n], k[:, :(j + 1) * n],
                 v[:, :(j + 1) * n], seg[:, j * n:(j + 1) * n],
                 seg[:, :(j + 1) * n], j * n, m, precision, q_block)
         for j in range(parts)], axis=1)


def _attend(q, k, v, sq, sk, q0, m: Model, precision, q_block):
    """Queries at positions q0.. of the row against keys 0.. of it."""
    B, S, H, hd = q.shape
    K = m.kv_heads
    qb = math.gcd(S, q_block)
    ik = jnp.arange(k.shape[1])
    qg = q.reshape(B, S // qb, qb, K, H // K, hd)

    @jax.checkpoint
    def block(args):
        qi, si, ii = args                     # (B,qb,K,G,hd), (B,qb), (qb,)
        sc = mm("bqkgd,bskd->bkgqs", qi, k, precision) / math.sqrt(hd)
        ok = ((si[:, :, None] == sk[:, None, :]) & (si[:, :, None] > 0)
              & (ii[:, None] >= ik[None, :])[None])
        sc = jnp.where(ok[:, None, None], sc, -1e30)
        p = jax.nn.softmax(sc, axis=-1)
        return mm("bkgqs,bskd->bqkgd", p, v, precision)

    out = jax.lax.map(block, (jnp.moveaxis(qg, 1, 0),
                              jnp.moveaxis(sq.reshape(B, S // qb, qb), 1, 0),
                              (q0 + jnp.arange(S)).reshape(S // qb, qb)))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H * hd)


def layer(p: dict, x, pos, seg, m: Model, precision):
    """One decoder layer on float32 parameters ``p`` ({attn, ffn})."""
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
    o = attention(q, k, v, seg, m, precision)
    x = x + mm("bse,ed->bsd", o, a["wo"], precision)
    f = p["ffn"]
    h = rmsnorm(x, f["norm"], m.rms_eps)
    g = jax.nn.silu(mm("bsd,df->bsf", h, f["w_gate"], precision)) \
        * mm("bsd,df->bsf", h, f["w_up"], precision)
    return x + mm("bsf,fd->bsd", g, f["w_down"], precision)


# ------------------------------------------------------------------ steps

class Reference:
    """Runs the reference's first steps on the cell's devices."""

    def __init__(self, model: dict, optim: dict, devices, *,
                 precision: str = "f32", loss_chunk: int = 1024):
        self.m = Model.from_config(model)
        self.o = Optim.from_config(optim)
        self.precision = precision
        self.loss_chunk = loss_chunk
        self.mesh = Mesh(np.array(devices), ("d",))
        self.n = len(devices)
        self._jits = {}

    # -- placement: rows over the devices, each leaf on its widest axis
    def _spec(self, shape, skip_first: bool = False):
        axes = [None] * len(shape)
        for i in sorted(range(int(skip_first), len(shape)),
                        key=lambda i: -shape[i]):
            if shape[i] % self.n == 0:
                axes[i] = "d"
                break
        return NamedSharding(self.mesh, P(*axes))

    def _rows(self, shape):
        axes = ["d" if shape[0] % self.n == 0 else None] + \
            [None] * (len(shape) - 1)
        return NamedSharding(self.mesh, P(*axes))

    def init(self, seed: int) -> dict:
        key = jax.random.PRNGKey(seed)
        if "init" not in self._jits:
            shapes = jax.eval_shape(lambda k: init_params(self.m, k), key)
            shard = _map_paths(lambda p, s: self._spec(
                s.shape, p.startswith("layers")), shapes)
            self._jits["init"] = jax.jit(lambda k: init_params(self.m, k),
                                         out_shardings=shard)
        return self._jits["init"](key)

    def _jit(self, name, fn, **kw):
        if name not in self._jits:
            self._jits[name] = jax.jit(fn, **kw)
        return self._jits[name]

    # -- the jitted pieces
    def _embed(self, e, tokens):
        return jnp.take(e.astype(F32), tokens, axis=0)

    def _layer_params(self, stacked, l):
        return jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_index_in_dim(x, l, 0, False).astype(F32),
            stacked)

    def _fwd(self, stacked, l, x, pos, seg):
        return layer(self._layer_params(stacked, l), x, pos, seg, self.m,
                     self.precision)

    def _bwd(self, stacked, l, x, pos, seg, dy):
        p = self._layer_params(stacked, l)
        _, vjp = jax.vjp(lambda xx, pp: layer(pp, xx, pos, seg, self.m,
                                              self.precision), x, p)
        return vjp(dy)

    def _head(self, x, s, w, labels, weights):
        """Loss, and its gradient for the last layer's output, the final
        norm's scale and the head, ``loss_chunk`` tokens at a time with
        the softmax gradient written out (one pass over the logits)."""
        m, prec = self.m, self.precision
        h, norm_vjp = jax.vjp(lambda x, s: rmsnorm(x, s, m.rms_eps), x,
                              s.astype(F32))
        w = w.astype(F32)
        D = h.shape[-1]
        n = h.shape[0] * h.shape[1]
        c = math.gcd(n, self.loss_chunk)
        wt = jnp.where(labels >= 0, weights, 0.0)
        wsum = jnp.sum(wt)
        wt = wt / jnp.where(wsum > 0, wsum, 1.0)
        cols = jnp.arange(m.vocab_padded)

        def one(gw, args):
            hc, lc, wc = args
            z = mm("cd,dv->cv", hc, w, prec)
            z = jnp.where(cols < m.vocab, z, -jnp.inf)
            lse = jax.nn.logsumexp(z, -1)
            hot = cols == jnp.clip(lc, 0)[:, None]
            ll = jnp.sum(jnp.where(hot, z, 0.0), -1)
            dz = (jnp.exp(z - lse[:, None]) - hot) * wc[:, None]
            gw = gw + mm("cd,cv->dv", hc, dz, prec)
            return gw, (jnp.sum((lse - ll) * wc), mm("cv,dv->cd", dz, w,
                                                      prec))

        gw, (ls, dh) = jax.lax.scan(
            one, jnp.zeros(w.shape, F32),
            (h.reshape(n // c, c, D), labels.reshape(n // c, c),
             wt.reshape(n // c, c)))
        dx, ds = norm_vjp(dh.reshape(h.shape))
        return jnp.sum(ls), (dx, ds, gw)

    def _colnorm_step(self, p, g, lr):
        """bf16(p - lr * g / (||g_col|| + eps)), and ||g||^2."""
        ss = jnp.sum(g * g, axis=-2, keepdims=True)
        new = p.astype(F32) - lr * g / (jnp.sqrt(ss) + self.o.norm_eps)
        return new.astype(p.dtype), jnp.sum(ss)

    def _colnorm_layer(self, stacked_leaf, l, g, lr):
        p = jax.lax.dynamic_index_in_dim(stacked_leaf, l, 0, False)
        new, ss = self._colnorm_step(p, g, lr)
        return jax.lax.dynamic_update_index_in_dim(stacked_leaf, new, l,
                                                   0), ss

    def _embed_step(self, e, tokens, dx, lr):
        g = jnp.zeros(e.shape, F32).at[tokens.reshape(-1)].add(
            dx.reshape(-1, dx.shape[-1]))
        return self._colnorm_step(e, g, lr)

    def _head_step(self, w, mom, g, scale, lr):
        mom = self.o.beta * mom + (1 - self.o.beta) * scale * g
        ss = jnp.sum(mom * mom, axis=-2, keepdims=True)
        new = w.astype(F32) - lr * mom / (jnp.sqrt(ss) + self.o.norm_eps)
        return new.astype(w.dtype), mom

    def _adam(self, p, mu, nu, g, scale, lr, count):
        o = self.o
        g = scale * g
        mu = o.adam_b1 * mu + (1 - o.adam_b1) * g
        nu = o.adam_b2 * nu + (1 - o.adam_b2) * g * g
        mh = mu / (1 - o.adam_b1 ** (count + 1))
        vh = nu / (1 - o.adam_b2 ** (count + 1))
        u = (-lr * mh / (jnp.sqrt(vh) + o.adam_eps)).astype(p.dtype)
        return (p.astype(F32) + u.astype(F32)).astype(p.dtype), mu, nu

    # -- one step
    def step(self, params: dict, state: dict, batch: dict, count: int):
        """Train one step in place of ``params``/``state``. Returns the loss,
        the norms of the gradient as the optimizer got it (after the clip)
        for the leaves it keeps state for, and of every leaf's gradient
        before the clip."""
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
        loss, (dx, g_s, g_w) = self._jit("head", self._head)(
            xs.pop(), params["final_norm"]["s"], params["lm_head"]["w"],
            b["labels"], b["loss_weights"])
        vec = {"final_norm/s": g_s}
        sq = {"lm_head/w": jnp.sum(g_w * g_w)}
        bwd = self._jit("bwd", self._bwd)
        col = self._jit("colnorm_layer", self._colnorm_layer,
                        donate_argnums=0)
        per_layer = []
        for l in reversed(range(m.layers)):
            dx, gp = bwd(lay, l, xs.pop(), pos, seg, dx)
            for sub in gp:
                for leaf in [n for n in gp[sub] if is_matrix(n)]:
                    lay[sub][leaf], s = col(lay[sub][leaf], l,
                                            gp[sub].pop(leaf), lr)
                    sq.setdefault(f"layers/{sub}/{leaf}", [None] * m.layers)
                    sq[f"layers/{sub}/{leaf}"][l] = s
            per_layer.append(gp)
        params["tok_embed"]["w"], sq["tok_embed/w"] = self._jit(
            "embed_step", self._embed_step, donate_argnums=0)(
                params["tok_embed"]["w"], tok, dx, lr)
        per_layer.reverse()
        for sub in lay:
            for leaf in lay[sub]:
                if not is_matrix(leaf):
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
        kept = set(vec) | {"lm_head/w"}
        got = {k: scale * v for k, v in grad_all.items()
               if k.split("[")[0] in kept}
        params["lm_head"]["w"], state["mom"] = self._jit(
            "head_step", self._head_step, donate_argnums=(0, 1))(
                params["lm_head"]["w"], state["mom"], g_w, scale, lr)
        adam = self._jit("adam", self._adam, donate_argnums=(0, 1, 2))
        for path, g in vec.items():
            node, leaf = _node(params, path)
            node[leaf], state["mu"][path], state["nu"][path] = adam(
                node[leaf], state["mu"][path], state["nu"][path], g, scale,
                lr, count)
        return float(loss), got, grad_all

    def init_state(self, params: dict) -> dict:
        def zeros(x):
            return jax.device_put(jnp.zeros(x.shape, F32), x.sharding)
        vec = {p: x for p, x in leaf_paths(params)
               if not is_matrix(p.rsplit("/", 1)[-1])}
        return {"mom": zeros(params["lm_head"]["w"]),
                "mu": {p: zeros(x) for p, x in vec.items()},
                "nu": {p: zeros(x) for p, x in vec.items()}}

    def run(self, seed: int, batches: list, against: dict | None = None,
            keep_params: bool = False) -> dict:
        """The reference's readings over ``len(batches)`` steps: losses,
        first-step gradients (``grad`` after the clip for the leaves with
        optimizer state, ``grad_all`` before it for every leaf) and each
        leaf's change (``delta``).

        ``against`` maps a label to another run's parameters after the same
        steps ({path: host array}, as ``keep_params`` returns them under
        ``params``); ``out["against"][label]`` then holds that run's change
        of each leaf (``delta``) and its distance from the reference's
        parameters (``diff``), by the same norms."""
        params = self.init(seed)
        state = self.init_state(params)
        out = {"loss": []}
        for i, batch in enumerate(batches):
            loss, got, grad_all = self.step(params, state, batch, i)
            out["loss"].append(loss)
            if i == 0:
                out["grad"], out["grad_all"] = got, grad_all
        del state
        p0 = self.init(seed)
        out["delta"] = named_norms(dict(leaf_paths(
            _diff_norms(params, p0))))
        out["against"] = {k: self.distances(v, params, p0)
                          for k, v in (against or {}).items()}
        if keep_params:
            out["params"] = jax.device_get(dict(leaf_paths(params)))
        return out

    def distances(self, other: dict, params: dict, p0: dict) -> dict:
        """Per leaf of ``other`` (host arrays), the norms of ``other - p0``
        and of ``other - params``, one leaf on the device at a time."""
        delta, diff = {}, {}
        ref, init = dict(leaf_paths(params)), dict(leaf_paths(p0))
        for path, x in other.items():
            y = jax.device_put(np.asarray(x), ref[path].sharding)
            stacked = path.startswith("layers")
            delta[path] = _norm_of_diff(y, init[path], stacked)
            diff[path] = _norm_of_diff(y, ref[path], stacked)
            del y
        return {"delta": named_norms(jax.device_get(delta)),
                "diff": named_norms(jax.device_get(diff))}


def _node(tree: dict, path: str):
    *head, leaf = path.split("/")
    for k in head:
        tree = tree[k]
    return tree, leaf


def _map_paths(fn, tree: dict, prefix: str = ""):
    return {k: (_map_paths(fn, v, f"{prefix}/{k}" if prefix else k)
                if isinstance(v, dict)
                else fn(f"{prefix}/{k}" if prefix else k, v))
            for k, v in tree.items()}


def stacked_norms(x):
    """Norm of each layer's slice of a stacked leaf (layer on axis 0)."""
    x = x.astype(F32)
    return jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))


@functools.partial(jax.jit, static_argnums=2)
def _norm_of_diff(x, y, stacked: bool):
    d = x.astype(F32) - y.astype(F32)
    return stacked_norms(d) if stacked else jnp.sqrt(jnp.sum(d * d))


@jax.jit
def _diff_norms(a, b):
    def one(path, x, y):
        d = x.astype(F32) - y.astype(F32)
        return stacked_norms(d) if path.startswith("layers") \
            else jnp.sqrt(jnp.sum(d * d))
    return _map_paths(lambda p, xy: one(p, *xy),
                      jax.tree_util.tree_map(lambda x, y: (x, y), a, b))
