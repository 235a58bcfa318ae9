"""The benchmark's own weights, data and plain reference for the dense
decoder family (minicpm-2b, qwen2-vl-2b), written from the configuration
files alone: it imports nothing of the program.

- ``init_params``: random weights from the seed, on the device, in one
  jitted call, laid out as the program's parameter tree.  Every matrix is
  N(0, 0.02) in the served type, every norm scale is one; the values of
  each stacked layer come from their own key, so the reference can make
  one layer at a time.
- ``train_batches``: token batches (and the vision-stub embeddings) from
  the seed, on the device.
- The reference forward: RMSNorm, RoPE (M-RoPE for the vision stub),
  causal softmax attention with grouped KV heads, SwiGLU, tied or untied
  head, computed in float32 at the highest matmul precision.  ``mm``
  chooses the precision of every matrix product; ``fp8`` is the control:
  both operands rounded to float8_e4m3 with a per-tensor scale.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
STD = 0.02
GRID_W = 32            # width of the vision stub's (h, w) position grid


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    eps: float
    theta: float
    tied: bool
    mrope: Tuple[int, ...] = ()
    vision_tokens: int = 0
    dtype: str = "bfloat16"

    @staticmethod
    def of(config: Dict[str, Any]) -> "Dims":
        c = config
        mrope = tuple(c.get("rope_scaling", {}).get("mrope_section", ()))
        return Dims(d=c["hidden_size"], layers=c["num_hidden_layers"],
                    heads=c["num_attention_heads"],
                    kv_heads=c["num_key_value_heads"],
                    head_dim=c.get("head_dim") or (c["hidden_size"]
                                                   // c["num_attention_heads"]),
                    ffn=c["intermediate_size"], vocab=c["vocab_size"],
                    eps=c["rms_norm_eps"], theta=c["rope_theta"],
                    tied=c["tie_word_embeddings"], mrope=mrope,
                    vision_tokens=c.get("vision_tokens", 0),
                    dtype=c["torch_dtype"])


def root_key(seed: int):
    """A PRNG key from any non-negative whole number (more than 32 bits)."""
    k = jax.random.PRNGKey(0)
    for part in (seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, seed >> 64):
        k = jax.random.fold_in(k, np.uint32(part & 0xFFFFFFFF))
    return k


# (path, shape without the layer axis, init, stacked over layers)
def leaves(dm: Dims) -> List[Tuple[Tuple[str, ...], Tuple[int, ...], str,
                                   bool]]:
    d, hd = dm.d, dm.head_dim
    out = [(("embed", "table"), (dm.vocab, d), "normal", False),
           (("units", "ln1", "scale"), (d,), "ones", True),
           (("units", "attn", "wq", "w"), (d, dm.heads * hd), "normal", True),
           (("units", "attn", "wk", "w"), (d, dm.kv_heads * hd), "normal",
            True),
           (("units", "attn", "wv", "w"), (d, dm.kv_heads * hd), "normal",
            True),
           (("units", "attn", "wo", "w"), (dm.heads * hd, d), "normal", True),
           (("units", "ln2", "scale"), (d,), "ones", True),
           (("units", "mlp", "gate", "w"), (d, dm.ffn), "normal", True),
           (("units", "mlp", "up", "w"), (d, dm.ffn), "normal", True),
           (("units", "mlp", "down", "w"), (dm.ffn, d), "normal", True),
           (("ln_f", "scale"), (d,), "ones", False)]
    if not dm.tied:
        out.append((("lm_head", "w"), (d, dm.vocab), "normal", False))
    return out


def _leaf(key, i: int, shape, kind: str, layer=None, dtype=jnp.bfloat16):
    if kind == "ones":
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, i)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    return (STD * jax.random.normal(k, shape, jnp.float32)).astype(dtype)


def _put(tree: Dict[str, Any], path, value) -> None:
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = value


def init_params(dm: Dims, seed: int):
    """The whole parameter tree in the served type, in one jitted call."""
    dtype = jnp.dtype(dm.dtype)

    @jax.jit
    def make(key):
        tree: Dict[str, Any] = {}
        for i, (path, shape, kind, stacked) in enumerate(leaves(dm)):
            if stacked:
                v = jax.vmap(lambda l: _leaf(key, i, shape, kind, l, dtype))(
                    jnp.arange(dm.layers))
            else:
                v = _leaf(key, i, shape, kind, None, dtype)
            _put(tree, path, v)
        return tree
    return make(root_key(seed))


def _layer_params(dm: Dims, key, layer):
    """Layer ``layer``'s weights, as served, in float32."""
    out: Dict[str, Any] = {}
    for i, (path, shape, kind, stacked) in enumerate(leaves(dm)):
        if stacked:
            _put(out, path[1:], _leaf(key, i, shape, kind, layer,
                                      jnp.dtype(dm.dtype)).astype(jnp.float32))
    return out


def top_params(dm: Dims, seed: int):
    """Embedding, final norm and head, as served, in float32."""
    key = root_key(seed)
    out: Dict[str, Any] = {}
    for i, (path, shape, kind, stacked) in enumerate(leaves(dm)):
        if not stacked:
            _put(out, path, _leaf(key, i, shape, kind, None,
                                  jnp.dtype(dm.dtype)).astype(jnp.float32))
    return out


def train_batches(dm: Dims, seed: int, member: int, batch: int, seq: int,
                  n: int) -> List[Dict[str, jnp.ndarray]]:
    """``n`` distinct training batches for one job, on the device:
    uniform tokens, next-token labels, and for the vision stub its patch
    embeddings, N(0, 0.02) in the served type."""
    dtype = jnp.dtype(dm.dtype)

    @jax.jit
    def make(key):
        kt, kv = jax.random.split(key)
        toks = jax.random.randint(kt, (batch, seq + 1), 0, dm.vocab,
                                  jnp.int32)
        out = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
        if dm.vision_tokens:
            out["vision_embeds"] = (STD * jax.random.normal(
                kv, (batch, dm.vision_tokens, dm.d))).astype(dtype)
        return out
    base = jax.random.fold_in(root_key(seed), 1_000 + member)
    return [make(jax.random.fold_in(base, j)) for j in range(n)]


# ---------------------------------------------------------------------- #
# matrix products
# ---------------------------------------------------------------------- #
def _fp8(x):
    """Round to float8_e4m3 with a per-tensor scale, back to float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def make_mm(precision: str):
    """``mm(subscripts, a, b)``: an einsum in float32 at the highest
    precision, or with both operands first rounded to fp8 (the control)."""
    if precision == "float32":
        return lambda eq, a, b: jnp.einsum(eq, a, b, precision=HIGHEST)
    if precision == "fp8":
        return lambda eq, a, b: jnp.einsum(eq, _fp8(a), _fp8(b),
                                           precision=HIGHEST)
    raise ValueError(f"unknown precision {precision!r}")


# ---------------------------------------------------------------------- #
# forward
# ---------------------------------------------------------------------- #
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope_angles(dm: Dims, positions):
    """(S,) positions -> (S, head_dim / 2) angles; with M-RoPE the
    vision-stub prefix takes (t=0, h=p // 32, w=p % 32) and text takes
    t = h = w = p, split over the rotary channels by ``mrope``."""
    inv = 1.0 / dm.theta ** (jnp.arange(0, dm.head_dim, 2, dtype=jnp.float32)
                             / dm.head_dim)
    p = positions.astype(jnp.float32)
    if not dm.mrope:
        return p[:, None] * inv
    vis = positions < dm.vision_tokens
    t = jnp.where(vis, 0, positions).astype(jnp.float32)
    h = jnp.where(vis, positions // GRID_W, positions).astype(jnp.float32)
    w = jnp.where(vis, positions % GRID_W, positions).astype(jnp.float32)
    a, b, _ = dm.mrope
    return jnp.concatenate([t[:, None] * inv[:a], h[:, None] * inv[a:a + b],
                            w[:, None] * inv[a + b:]], axis=-1)


def _rotate(x, ang):
    """x (B, S, H, D); ang (S, D/2): rotate the two halves of D."""
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def layer_forward(dm: Dims, mm, w, x, ang):
    """One decoder layer over x (B, S, D) float32, causal over S."""
    b, s, _ = x.shape
    hd, g = dm.head_dim, dm.heads // dm.kv_heads
    h = _rms(x, w["ln1"]["scale"], dm.eps)
    q = mm("bsd,de->bse", h, w["attn"]["wq"]["w"]).reshape(b, s, dm.heads, hd)
    k = mm("bsd,de->bse", h, w["attn"]["wk"]["w"]).reshape(
        b, s, dm.kv_heads, hd)
    v = mm("bsd,de->bse", h, w["attn"]["wv"]["w"]).reshape(
        b, s, dm.kv_heads, hd)
    q, k = _rotate(q, ang), _rotate(k, ang)
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    scores = mm("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    o = mm("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + mm("bse,ed->bsd", o.reshape(b, s, dm.heads * hd),
               w["attn"]["wo"]["w"])
    h = _rms(x, w["ln2"]["scale"], dm.eps)
    f = jax.nn.silu(mm("bsd,df->bsf", h, w["mlp"]["gate"]["w"])) * mm(
        "bsd,df->bsf", h, w["mlp"]["up"]["w"])
    return x + mm("bsf,fd->bsd", f, w["mlp"]["down"]["w"])


def embed_tokens(dm: Dims, top, batch):
    x = top["embed"]["table"][batch["tokens"]]
    if dm.vision_tokens and "vision_embeds" in batch:
        tv = batch["vision_embeds"].shape[1]
        x = jnp.concatenate([batch["vision_embeds"].astype(jnp.float32),
                             x[:, tv:]], axis=1)
    return x


def head(dm: Dims, mm, top, x):
    x = _rms(x, top["ln_f"]["scale"], dm.eps)
    if dm.tied:
        return mm("bsd,vd->bsv", x, top["embed"]["table"])
    return mm("bsd,dv->bsv", x, top["lm_head"]["w"])


def forward(dm: Dims, mm, params, batch):
    """Logits (B, S, V) of the whole model from a float32 parameter tree
    in the program's layout."""
    s = batch["tokens"].shape[1]
    ang = rope_angles(dm, jnp.arange(s))
    x = embed_tokens(dm, params, batch)
    for layer in range(dm.layers):
        w = jax.tree.map(lambda a: a[layer], params["units"])
        x = layer_forward(dm, mm, w, x, ang)
    return head(dm, mm, params, x)


# ---------------------------------------------------------------------- #
# training
# ---------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class Adam:
    lr: float
    b1: float
    b2: float
    eps: float
    weight_decay: float


def loss(dm: Dims, mm, params, batch):
    logits = forward(dm, mm, params, batch)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, batch["labels"][..., None], -1)[..., 0]
    return -jnp.mean(ll)


def make_train_step(dm: Dims, precision: str, opt: Adam):
    """One plain step: loss and gradient over the whole batch, then AdamW
    in float32; parameters are kept in the served type, as the
    configuration states."""
    mm = make_mm(precision)
    served = jnp.dtype(dm.dtype)

    @jax.jit
    def step(params, m, v, t, batch):
        lval, g = jax.value_and_grad(lambda p: loss(dm, mm, p, batch))(params)
        t = t + 1
        c1, c2 = 1 - opt.b1 ** t, 1 - opt.b2 ** t

        def upd(p, g, m, v):
            m = opt.b1 * m + (1 - opt.b1) * g
            v = opt.b2 * v + (1 - opt.b2) * g * g
            delta = (m / c1) / (jnp.sqrt(v / c2) + opt.eps) \
                + opt.weight_decay * p
            return (p - opt.lr * delta).astype(served).astype(jnp.float32), m, v
        out = jax.tree.map(upd, params, g, m, v)
        pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                      is_leaf=lambda o: isinstance(o, tuple))
        return pick(0), pick(1), pick(2), t, lval, g
    return step


def leaf_norms(tree) -> np.ndarray:
    return np.asarray(jax.jit(lambda t: jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
        for x in jax.tree.leaves(t)]))(tree))


@jax.jit
def _gap_norms(p, q):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                                  - b.astype(jnp.float32))))
                      for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(q))])


def change_norms(dm: Dims, seed: int, params) -> np.ndarray:
    """Per-leaf norm of ``params`` minus the seed's initial weights, which
    are made again for the purpose."""
    return np.asarray(_gap_norms(params, init_params(dm, seed)))


def run_reference(dm: Dims, seed: int, batches: Sequence[Dict[str, Any]],
                  opt: Adam, precision: str = "float32",
                  keep_rows: int | None = None) -> Dict[str, Any]:
    """Train from the seed's weights over ``batches`` (one per step).
    ``keep_rows`` keeps only the first rows of every batch (a planted
    fault).  Returns per-step losses, the first gradient's per-leaf norms
    and the per-leaf norms of the parameters' change."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          init_params(dm, seed))
    p0 = params
    zeros = jax.tree.map(jnp.zeros_like, params)
    m, v, t = zeros, zeros, jnp.zeros((), jnp.float32)
    step = make_train_step(dm, precision, opt)
    losses, grad = [], None
    with jax.default_matmul_precision("highest"):
        for b in batches:
            if keep_rows is not None:
                b = jax.tree.map(lambda x: x[:keep_rows], b)
            params, m, v, t, lval, g = step(params, m, v, t, b)
            losses.append(float(lval))
            if grad is None:
                grad = leaf_norms(g)
            del g
        change = np.asarray(_gap_norms(params, p0))
    return {"losses": losses, "grad": grad, "change": change}


# ---------------------------------------------------------------------- #
# serving
# ---------------------------------------------------------------------- #
def served_logits(dm: Dims, seed: int, seqs: Sequence[Sequence[int]],
                  starts: Sequence[int], pad_to: int,
                  precisions: Sequence[str] = ("float32",)
                  ) -> Dict[str, List[Dict[str, np.ndarray]]]:
    """Teacher-forced logits over each sequence (prompt + served tokens),
    layer by layer so that one layer's weights are on the device at a
    time.  For each sequence and each position ``p`` in ``[start - 1,
    len - 1)`` (the rows that chose a served token) returns, per
    precision: the largest logit, the logit of the token that was served
    at ``p + 1``, and the arg-max token; the float32 entry also holds the
    float32 logit of every other precision's arg-max."""
    n = len(seqs)
    toks = np.zeros((n, pad_to), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    toks = jnp.asarray(toks)
    ang = rope_angles(dm, jnp.arange(pad_to))
    top = top_params(dm, seed)
    make_layer = jax.jit(functools.partial(_layer_params, dm))
    key = root_key(seed)
    states = {}
    with jax.default_matmul_precision("highest"):
        for prec in precisions:
            mm = make_mm(prec)
            step = jax.jit(lambda w, x, a, mm=mm: layer_forward(dm, mm, w, x,
                                                                a))
            xs = [top["embed"]["table"][toks[i]][None] for i in range(n)]
            for layer in range(dm.layers):
                w = make_layer(key, layer)
                xs = [step(w, x, ang) for x in xs]
                del w
            states[prec] = xs
        out: Dict[str, List[Dict[str, np.ndarray]]] = {p: [] for p in
                                                       precisions}
        for prec in precisions:
            mm = make_mm(prec)

            @jax.jit
            def rows(top, x, nxt, mm=mm):
                lg = head(dm, mm, top, x)[0]                # (S, V)
                best = jnp.max(lg, -1)
                pick = jnp.take_along_axis(lg, nxt[:, None], -1)[:, 0]
                return best, pick, jnp.argmax(lg, -1)
            for i, s in enumerate(seqs):
                nxt = jnp.concatenate([toks[i, 1:], jnp.zeros((1,),
                                                              jnp.int32)])
                best, pick, arg = rows(top, states[prec][i], nxt)
                lo, hi = starts[i] - 1, len(s) - 1
                out[prec].append({"best": np.asarray(best)[lo:hi],
                                  "served": np.asarray(pick)[lo:hi],
                                  "argmax": np.asarray(arg)[lo:hi]})
        ref = out["float32"]

        @jax.jit
        def logit_of(top, x, tok):
            lg = head(dm, make_mm("float32"), top, x)[0]
            return jnp.take_along_axis(lg, tok[:, None], -1)[:, 0]
        for prec in precisions:
            if prec == "float32":
                continue
            for i, s in enumerate(seqs):
                lo, hi = starts[i] - 1, len(s) - 1
                tok = np.zeros(pad_to, np.int32)
                tok[lo:hi] = out[prec][i]["argmax"]
                ref[i][prec + "_argmax_logit"] = np.asarray(logit_of(
                    top, states["float32"][i], jnp.asarray(tok)))[lo:hi]
    return out
