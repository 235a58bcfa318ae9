"""The benchmark's own weights, data and plain float32 reference for the
hybrid of Mamba2 and attention layers with an MLP after every mixer
(granite-4.0-h-micro, ``model_type: granitemoehybrid``), written from the
configuration file alone: it imports nothing of the program.

The model, as the configuration states it:

- ``x0 = embed(tokens) * embedding_multiplier``;
- layer ``l``: ``h = x + r * mixer_l(RMSNorm(x))``, then ``x = h + r *
  MLP(RMSNorm(h))``, ``r = residual_multiplier``; the MLP is SwiGLU
  ``d -> 2 x shared_intermediate_size -> d`` (gate, then up);
- ``mixer_l`` is ``layer_types[l]``: ``attention`` is causal attention
  with grouped KV heads, no bias, no position encoding, softmax scale
  ``attention_multiplier``; ``mamba`` is Mamba2 with ``mamba_n_heads``
  heads of ``mamba_d_head``, state ``mamba_d_state``, one group: ``z, xBC,
  dt = in_proj(x)``, ``xBC = silu(causal depthwise conv(xBC) + bias)``,
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``, then per head the
  recurrence ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``y_t = h_t
  C_t + D x_t``, the gated norm ``RMSNorm(y * silu(z))`` and ``out_proj``;
- head: RMSNorm, the tied embedding, ``/ logits_scaling``.

Mamba2 is computed as that recurrence, position by position, and not as
the chunked SSD: the reference shares no algorithm with the program's
chunked form or its kernel.  Everything is float32 at the highest matmul
precision; ``mm`` chooses the precision of every matrix product (``fp8``
is the control, as in ``bench/reference.py``).

The gradient is taken layer by layer (each layer's vjp from the saved
layer inputs, the head and the attention in blocks of positions, the
recurrence recomputed in blocks of positions), and AdamW is applied to a
layer as soon as its gradient is known, so that the reference fits on
one chip beside nothing else.

Departures from the published model:

- random weights from the seed, not the published checkpoint: matrices
  N(0, 0.02), norm scales one; ``A_log = log(1..heads)``, ``dt_bias`` the
  inverse softplus of dt drawn log-uniformly in [1e-3, 0.1] (floor
  1e-4), ``D`` one (Mamba2's own init); the depthwise conv's weight and
  bias U(-1/2, 1/2) (PyTorch's default for 4 taps);
- the MLP's ``input_linear`` (``d -> 2 x ffn``) is held as two matrices,
  ``gate`` and ``up``, as the program holds it: the same products;
- the first ``num_hidden_layers`` entries of ``layer_types`` are run;
- weights are kept in the configuration's type between steps (float32
  for ``A_log``, ``dt_bias`` and ``D``, as the program keeps them).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import (STD, Adam, _gap_norms, _put, _rms, leaf_norms,
                             make_mm, root_key)

__all__ = ["Adam", "Dims", "change_norms", "init_params", "leaf_norms",
           "run_reference", "train_batches"]

KINDS = ("mamba", "attention")
BLOCK = 64              # positions per recomputed block of the recurrence
Q_BLOCK = 512           # query positions per block of attention
HEAD_BLOCK = 256        # positions per block of the head's loss


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    kinds: Tuple[str, ...]          # the layers run, in order
    period: int                     # layers in one published period
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    eps: float
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    conv: int
    chunk: int
    emb_mult: float
    res_mult: float
    attn_mult: float
    logits_scaling: float
    dtype: str = "bfloat16"

    @property
    def layers(self) -> int:
        return len(self.kinds)

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @staticmethod
    def of(config: Dict[str, Any]) -> "Dims":
        c = config
        types = tuple(c["layer_types"])
        n = c["num_hidden_layers"]
        if set(types) - set(KINDS) or c["mamba_n_groups"] != 1 \
                or not c["tie_word_embeddings"] \
                or c["intermediate_size"] != c["shared_intermediate_size"]:
            raise ValueError("the reference runs one-group Mamba2 and "
                             "attention layers with one MLP width and a "
                             "tied head")
        period = next(p for p in range(1, len(types) + 1)
                      if len(types) % p == 0
                      and types == types[:p] * (len(types) // p))
        if n % period:
            raise ValueError(f"{n} layers are no whole number of periods "
                             f"of {period}")
        return Dims(d=c["hidden_size"], kinds=types[:n], period=period,
                    heads=c["num_attention_heads"],
                    kv_heads=c["num_key_value_heads"],
                    head_dim=c["hidden_size"] // c["num_attention_heads"],
                    ffn=c["shared_intermediate_size"], vocab=c["vocab_size"],
                    eps=c["rms_norm_eps"], ssm_heads=c["mamba_n_heads"],
                    ssm_head_dim=c["mamba_d_head"],
                    ssm_state=c["mamba_d_state"], conv=c["mamba_d_conv"],
                    chunk=c["mamba_chunk_size"],
                    emb_mult=c["embedding_multiplier"],
                    res_mult=c["residual_multiplier"],
                    attn_mult=c["attention_multiplier"],
                    logits_scaling=c["logits_scaling"],
                    dtype=c.get("torch_dtype", "bfloat16"))


# ---------------------------------------------------------------------- #
# weights
# ---------------------------------------------------------------------- #
# (path, shape, init, kept in float32)
Leaf = Tuple[Tuple[str, ...], Tuple[int, ...], str, bool]


def layer_leaves(dm: Dims, kind: str) -> List[Leaf]:
    d, hd = dm.d, dm.head_dim
    if kind == "attention":
        mixer = [(("attn", "wq", "w"), (d, dm.heads * hd), "normal", False),
                 (("attn", "wk", "w"), (d, dm.kv_heads * hd), "normal",
                  False),
                 (("attn", "wv", "w"), (d, dm.kv_heads * hd), "normal",
                  False),
                 (("attn", "wo", "w"), (dm.heads * hd, d), "normal", False)]
    else:
        di, n, h = dm.d_inner, dm.ssm_state, dm.ssm_heads
        mixer = [(("mamba", "in_proj", "w"), (d, 2 * di + 2 * n + h),
                  "normal", False),
                 (("mamba", "conv_w"), (dm.conv, di + 2 * n), "conv", False),
                 (("mamba", "conv_b"), (di + 2 * n,), "conv", False),
                 (("mamba", "A_log"), (h,), "a_log", True),
                 (("mamba", "dt_bias"), (h,), "dt_bias", True),
                 (("mamba", "D"), (h,), "ones", True),
                 (("mamba", "norm", "scale"), (di,), "ones", False),
                 (("mamba", "out_proj", "w"), (di, d), "normal", False)]
    return ([(("ln1", "scale"), (d,), "ones", False)] + mixer
            + [(("ln2", "scale"), (d,), "ones", False),
               (("mlp", "gate", "w"), (d, dm.ffn), "normal", False),
               (("mlp", "up", "w"), (d, dm.ffn), "normal", False),
               (("mlp", "down", "w"), (dm.ffn, d), "normal", False)])


def _top_leaves(dm: Dims) -> List[Leaf]:
    return [(("embed", "table"), (dm.vocab, dm.d), "normal", False),
            (("ln_f", "scale"), (dm.d,), "ones", False)]


def _value(key, shape, init: str, dtype):
    if init == "ones":
        return jnp.ones(shape, dtype)
    if init == "normal":
        v = STD * jax.random.normal(key, shape, jnp.float32)
    elif init == "conv":
        v = jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5)
    elif init == "a_log":
        v = jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))
    elif init == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(0.1)))
        dt = jnp.maximum(dt, 1e-4)
        v = dt + jnp.log(-jnp.expm1(-dt))            # inverse softplus
    else:
        raise ValueError(init)
    return v.astype(dtype)


def _dtype(dm: Dims, f32: bool):
    return jnp.float32 if f32 else jnp.dtype(dm.dtype)


def _make_layer(dm: Dims, key, layer: int):
    """Layer ``layer``'s weights in their kept types: each leaf from its
    own key, so that a layer can be made alone."""
    base = jax.random.fold_in(jax.random.fold_in(key, 1), layer)
    out: Dict[str, Any] = {}
    for j, (path, shape, init, f32) in enumerate(
            layer_leaves(dm, dm.kinds[layer])):
        _put(out, path, _value(jax.random.fold_in(base, j), shape, init,
                               _dtype(dm, f32)))
    return out


def _make_top(dm: Dims, key):
    out: Dict[str, Any] = {}
    for j, (path, shape, init, f32) in enumerate(_top_leaves(dm)):
        _put(out, path, _value(jax.random.fold_in(jax.random.fold_in(
            key, 0), j), shape, init, _dtype(dm, f32)))
    return out


def to_program(dm: Dims, top, layers: Sequence[Any]):
    """The program's tree: ``units`` stacked over periods, ``sub<i>`` the
    i-th layer of a period."""
    units = {f"sub{i}": jax.tree.map(
        lambda *xs: jnp.stack(xs), *layers[i::dm.period])
        for i in range(dm.period)}
    return dict(top, units=units)


def init_params(dm: Dims, seed: int):
    """The whole parameter tree in the program's layout, in one jitted
    call."""
    @jax.jit
    def make(key):
        return to_program(dm, _make_top(dm, key),
                          [_make_layer(dm, key, l) for l in range(dm.layers)])
    return make(root_key(seed))


def _init_parts(dm: Dims, seed: int):
    """The seed's weights as the reference holds them: the top leaves and
    one tree per layer."""
    key = root_key(seed)
    make_layer = jax.jit(functools.partial(_make_layer, dm),
                         static_argnums=1)
    return (jax.jit(functools.partial(_make_top, dm))(key),
            [make_layer(key, l) for l in range(dm.layers)])


def train_batches(dm: Dims, seed: int, member: int, batch: int, seq: int,
                  n: int) -> List[Dict[str, jnp.ndarray]]:
    """``n`` distinct batches of uniform tokens and next-token labels for
    one job, on the device."""
    @jax.jit
    def make(key):
        toks = jax.random.randint(key, (batch, seq + 1), 0, dm.vocab,
                                  jnp.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    base = jax.random.fold_in(root_key(seed), 1_000 + member)
    return [make(jax.random.fold_in(base, j)) for j in range(n)]


# ---------------------------------------------------------------------- #
# forward, float32
# ---------------------------------------------------------------------- #
def _blocks(s: int, size: int) -> int:
    return math.gcd(s, size)


def attention(dm: Dims, mm, w, x):
    """Causal attention with grouped KV heads and no position encoding,
    over blocks of queries."""
    b, s, _ = x.shape
    hd, g = dm.head_dim, dm.heads // dm.kv_heads
    q = mm("bsd,de->bse", x, w["wq"]["w"]).reshape(b, s, dm.heads, hd)
    k = mm("bsd,de->bse", x, w["wk"]["w"]).reshape(b, s, dm.kv_heads, hd)
    v = mm("bsd,de->bse", x, w["wv"]["w"]).reshape(b, s, dm.kv_heads, hd)
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    qb = _blocks(s, Q_BLOCK)

    @jax.checkpoint
    def block(start):
        qi = jax.lax.dynamic_slice_in_dim(q, start, qb, axis=1)
        scores = mm("bqhd,bkhd->bhqk", qi, k) * dm.attn_mult
        rows = start + jnp.arange(qb)
        causal = rows[:, None] >= jnp.arange(s)[None, :]
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        return mm("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    o = jax.lax.map(block, jnp.arange(0, s, qb))          # (nq, b, qb, ...)
    o = jnp.moveaxis(o, 0, 1).reshape(b, s, dm.heads * hd)
    return mm("bse,ed->bsd", o, w["wo"]["w"])


def recurrence(mm, x, dt, A, Bm, Cm):
    """The Mamba2 state recurrence, one position at a time, without the
    ``D`` skip.  x (b, s, h, p), dt (b, s, h), A (h,), Bm/Cm (b, s, n) ->
    y (b, s, h, p).  Blocks of positions are recomputed in the backward."""
    b, s, h, p = x.shape
    n = Bm.shape[-1]
    t = _blocks(s, BLOCK)

    def step(state, inp):
        xt, dtt, bt, ct = inp            # (b,h,p) (b,h) (b,n) (b,n)
        state = (jnp.exp(dtt * A)[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * bt[:, None, None, :])
        return state, mm("bhpn,bn->bhp", state, ct)

    @jax.checkpoint
    def block(state, inp):
        return jax.lax.scan(step, state, inp)

    def time_major(a):
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((s // t, t) + a.shape[1:])
    _, ys = jax.lax.scan(block, jnp.zeros((b, h, p, n), jnp.float32),
                         tuple(map(time_major, (x, dt, Bm, Cm))))
    return jnp.moveaxis(ys.reshape((s,) + ys.shape[2:]), 0, 1)


def mamba(dm: Dims, mm, w, x):
    b, s, _ = x.shape
    di, n, h = dm.d_inner, dm.ssm_state, dm.ssm_heads
    proj = mm("bsd,de->bse", x, w["in_proj"]["w"])
    z, xbc, dt = (proj[..., :di], proj[..., di:2 * di + 2 * n],
                  proj[..., 2 * di + 2 * n:])
    pad = jnp.pad(xbc, ((0, 0), (dm.conv - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(pad[:, i:i + s] * w["conv_w"][i]
                          for i in range(dm.conv)) + w["conv_b"])
    xs = xbc[..., :di].reshape(b, s, h, dm.ssm_head_dim)
    bm, cm = xbc[..., di:di + n], xbc[..., di + n:]
    dt = jax.nn.softplus(dt + w["dt_bias"])
    y = recurrence(mm, xs, dt, -jnp.exp(w["A_log"]), bm, cm)
    y = (y + w["D"][:, None] * xs).reshape(b, s, di)
    y = _rms(y * jax.nn.silu(z), w["norm"]["scale"], dm.eps)
    return mm("bse,ed->bsd", y, w["out_proj"]["w"])


def layer_forward(dm: Dims, mm, kind: str, w, x):
    """One layer over x (B, S, D) float32."""
    mix = attention if kind == "attention" else mamba
    x = x + dm.res_mult * mix(dm, mm, w["attn" if kind == "attention"
                                       else "mamba"],
                              _rms(x, w["ln1"]["scale"], dm.eps))
    hn = _rms(x, w["ln2"]["scale"], dm.eps)
    f = jax.nn.silu(mm("bsd,df->bsf", hn, w["mlp"]["gate"]["w"])) * mm(
        "bsd,df->bsf", hn, w["mlp"]["up"]["w"])
    return x + dm.res_mult * mm("bsf,fd->bsd", f, w["mlp"]["down"]["w"])


def head(dm: Dims, mm, top, x):
    """Logits of the tied head over x (B, S, D)."""
    hn = _rms(x, top["ln_f"]["scale"], dm.eps)
    return mm("bsd,vd->bsv", hn, top["embed"]["table"]) / dm.logits_scaling


def head_loss(dm: Dims, mm, top, x, labels):
    """Mean next-token cross entropy of the head over x (B, S, D), in
    blocks of positions."""
    b, s, _ = x.shape
    t = _blocks(s, HEAD_BLOCK)

    @jax.checkpoint
    def block(start):
        xi = jax.lax.dynamic_slice_in_dim(x, start, t, axis=1)
        li = jax.lax.dynamic_slice_in_dim(labels, start, t, axis=1)
        logp = jax.nn.log_softmax(head(dm, mm, top, xi), axis=-1)
        return jnp.sum(jnp.take_along_axis(logp, li[..., None], -1))
    return -jnp.sum(jax.lax.map(block, jnp.arange(0, s, t))) / (b * s)


def forward(dm: Dims, mm, params, tokens):
    """Logits (B, S, V) of the whole model from a float32 parameter tree
    in the program's layout."""
    x = params["embed"]["table"][tokens] * dm.emb_mult
    for l, kind in enumerate(dm.kinds):
        w = jax.tree.map(lambda a: a[l // dm.period],
                         params["units"][f"sub{l % dm.period}"])
        x = layer_forward(dm, mm, kind, w, x)
    return head(dm, mm, params, x)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


# ---------------------------------------------------------------------- #
# training
# ---------------------------------------------------------------------- #
@functools.lru_cache(maxsize=None)
def _programs(dm: Dims, precision: str, opt: Adam):
    """The jitted pieces of one step: the embedding, each kind of layer
    forward and backward, the head's loss and gradient, the embedding's
    gradient and AdamW."""
    mm = make_mm(precision)

    def fwd(kind):
        return jax.jit(lambda w, x: layer_forward(dm, mm, kind, _f32(w), x))

    def bwd(kind):
        @jax.jit
        def run(w, x, dy):
            _, pull = jax.vjp(lambda w, x: layer_forward(dm, mm, kind, w, x),
                              _f32(w), x)
            return pull(dy)
        return run

    @jax.jit
    def embed(top, tokens):
        return top["embed"]["table"][tokens].astype(jnp.float32) \
            * dm.emb_mult

    @jax.jit
    def head(top, x, labels):
        return jax.value_and_grad(
            lambda t, x: head_loss(dm, mm, t, x, labels), argnums=(0, 1))(
                _f32(top), x)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def embed_grad(g_table, tokens, dx):
        return g_table.at[tokens].add(dx * dm.emb_mult)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def adam(p, m, v, g, t):
        c1, c2 = 1 - opt.b1 ** t, 1 - opt.b2 ** t

        def upd(p, m, v, g):
            m = opt.b1 * m + (1 - opt.b1) * g
            v = opt.b2 * v + (1 - opt.b2) * g * g
            pf = p.astype(jnp.float32)
            delta = (m / c1) / (jnp.sqrt(v / c2) + opt.eps) \
                + opt.weight_decay * pf
            return (pf - opt.lr * delta).astype(p.dtype), m, v
        out = jax.tree.map(upd, p, m, v, g)
        pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                      is_leaf=lambda o: isinstance(o, tuple))
        return pick(0), pick(1), pick(2)

    return {"fwd": {k: fwd(k) for k in KINDS},
            "bwd": {k: bwd(k) for k in KINDS}, "embed": embed,
            "head": head, "embed_grad": embed_grad, "adam": adam}


@jax.jit
def _squares(a, b=None):
    """Per-leaf sums of squares of ``a`` (or of ``a - b``), in float32."""
    if b is None:
        b = jax.tree.map(jnp.zeros_like, a)
    return jax.tree.map(lambda x, y: jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32))), a, b)


def _norms(dm: Dims, top_sq, layers_sq) -> np.ndarray:
    """Per-leaf norms in the program's leaf order from per-leaf sums of
    squares, a stacked leaf's taken over all its periods."""
    tree = to_program(dm, top_sq, layers_sq)
    return np.sqrt(np.array([float(jnp.sum(x))
                             for x in jax.tree.leaves(tree)]))


def change_norms(dm: Dims, seed: int, params) -> np.ndarray:
    """Per-leaf norm of ``params`` (the program's tree) minus the seed's
    initial weights, which are made again for the purpose."""
    return np.asarray(_gap_norms(params, init_params(dm, seed)))


def run_reference(dm: Dims, seed: int, batches: Sequence[Dict[str, Any]],
                  opt: Adam, precision: str = "float32",
                  keep_rows: int | None = None) -> Dict[str, Any]:
    """Train from the seed's weights over ``batches`` (one per step).
    ``keep_rows`` keeps only the first rows of every batch (a planted
    fault).  Returns per-step losses, the first gradient's per-leaf norms
    and the per-leaf norms of the parameters' change, each in the
    program's leaf order."""
    prog = _programs(dm, precision, opt)
    top, layers = _init_parts(dm, seed)
    zeros = lambda t: jax.tree.map(
        lambda a: jnp.zeros(a.shape, jnp.float32), t)
    mt, vt = zeros(top), zeros(top)
    ml, vl = [zeros(w) for w in layers], [zeros(w) for w in layers]
    losses, grad = [], None
    with jax.default_matmul_precision("highest"):
        for step, batch in enumerate(batches, start=1):
            if keep_rows is not None:
                batch = jax.tree.map(lambda a: a[:keep_rows], batch)
            t = jnp.asarray(step, jnp.float32)
            xs = [prog["embed"](top, batch["tokens"])]
            for l, kind in enumerate(dm.kinds):
                xs.append(prog["fwd"][kind](layers[l], xs[-1]))
            lval, (g_top, dx) = prog["head"](top, xs.pop(), batch["labels"])
            losses.append(float(lval))
            g_layers = [None] * dm.layers
            for l in reversed(range(dm.layers)):
                g, dx = prog["bwd"][dm.kinds[l]](layers[l], xs.pop(), dx)
                if step == 1:
                    g_layers[l] = _squares(g)
                layers[l], ml[l], vl[l] = prog["adam"](layers[l], ml[l],
                                                       vl[l], g, t)
            g_top["embed"]["table"] = prog["embed_grad"](
                g_top["embed"]["table"], batch["tokens"], dx)
            if step == 1:
                grad = _norms(dm, _squares(g_top), g_layers)
            top, mt, vt = prog["adam"](top, mt, vt, g_top, t)
            del dx, g_top
    del ml, vl, mt, vt
    top0, layers0 = _init_parts(dm, seed)
    change = _norms(dm, _squares(top, top0),
                    [_squares(a, b) for a, b in zip(layers, layers0)])
    return {"losses": losses, "grad": grad, "change": change}
