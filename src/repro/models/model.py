"""Family-dispatching LM assembly for the 10 assigned architectures.

Every architecture is built from a repeating *unit* (``cfg.pattern_unit()``
layers) whose parameters are stacked with a leading ``n_units`` dimension
and executed with ``lax.scan`` (scan-over-layers keeps the HLO small and
the FSDP all-gather working set at one unit; DESIGN.md §7).

Public API (all functional, params are plain pytrees):
    init_params(cfg, key, dtype)             -> params
    forward(cfg, params, batch, ...)         -> (logits, aux)
    init_cache(cfg, params, batch, max_len)  -> decode cache
    decode_step(cfg, params, cache, tokens, index) -> (logits, cache)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.sharding.hooks import constrain

from . import attention as attn_mod
from . import moe as moe_mod
from . import ssm as ssm_mod
from . import xlstm as xlstm_mod
from .layers import (embed, embed_init, gelu_mlp, gelu_mlp_init, linear,
                     linear_init, mrope_cos_sin, rms_norm, rms_norm_init,
                     rope_cos_sin, sinusoidal_positions, swiglu, swiglu_init,
                     unembed)


def _dtype(cfg: ArchConfig, override=None):
    if override is not None:
        return override
    return jnp.dtype(cfg.dtype)


def _stack(key, n: int, init_fn):
    """Stack ``n`` independent inits along a new leading axis."""
    keys = jax.random.split(key, n)
    inits = [init_fn(k) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *inits)


# ====================================================================== #
# per-family unit init
# ====================================================================== #
def _attn_block_init(key, cfg: ArchConfig, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": rms_norm_init(cfg.d_model, dtype),
        "attn": attn_mod.attention_init(
            k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            qkv_bias=cfg.qkv_bias, dtype=dtype),
        "ln2": rms_norm_init(cfg.d_model, dtype),
        "mlp": swiglu_init(k2, cfg.d_model, cfg.d_ff, dtype=dtype),
    }


def _moe_layer_init(key, cfg: ArchConfig, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": rms_norm_init(cfg.d_model, dtype),
        "attn": attn_mod.attention_init(
            k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            qkv_bias=cfg.qkv_bias, dtype=dtype),
        "ln2": rms_norm_init(cfg.d_model, dtype),
        "ffn": moe_mod.moe_init(
            k2, cfg.d_model, cfg.moe_experts, cfg.moe_d_ff or cfg.d_ff,
            shared_expert=cfg.moe_shared_expert,
            pad_to=getattr(cfg, "moe_pad_to", 0) or 0, dtype=dtype),
    }


def _unit_init(key, cfg: ArchConfig, dtype):
    fam = cfg.family
    if fam in ("dense", "vlm"):
        return _attn_block_init(key, cfg, dtype)
    if fam == "moe":
        u = cfg.pattern_unit()
        keys = jax.random.split(key, u)
        unit = {}
        for i in range(u):
            is_moe = (i == u - 1)   # MoE is the last layer of the unit
            if is_moe:
                unit[f"sub{i}"] = _moe_layer_init(keys[i], cfg, dtype)
            else:
                unit[f"sub{i}"] = _attn_block_init(keys[i], cfg, dtype)
        return unit
    if fam == "hybrid":            # zamba2: u mamba layers (+ shared attn)
        u = cfg.pattern_unit()

        def one(k):
            return {
                "ln": rms_norm_init(cfg.d_model, dtype),
                "mamba": ssm_mod.mamba2_init(
                    k, cfg.d_model, cfg.d_inner, cfg.ssm_state,
                    cfg.n_ssm_heads, cfg.ssm_conv, dtype=dtype),
            }
        return {"mamba": _stack(key, u, one)}
    if fam == "ssm":               # xlstm: (u-1) mLSTM + 1 sLSTM
        u = cfg.pattern_unit()
        km, ks = jax.random.split(key)

        def one(k):
            return {
                "ln": rms_norm_init(cfg.d_model, dtype),
                "mlstm": xlstm_mod.mlstm_init(
                    k, cfg.d_model, cfg.d_inner, cfg.n_heads, dtype=dtype),
            }
        unit = {"mlstm": _stack(km, max(1, u - 1), one)}
        if cfg.slstm_every:
            unit["slstm"] = {
                "ln": rms_norm_init(cfg.d_model, dtype),
                "slstm": xlstm_mod.slstm_init(
                    ks, cfg.d_model, cfg.n_heads, dtype=dtype),
            }
        return unit
    if fam == "audio":             # whisper decoder unit (cross-attn)
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "ln1": rms_norm_init(cfg.d_model, dtype),
            "attn": attn_mod.attention_init(
                k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                dtype=dtype),
            "lnx": rms_norm_init(cfg.d_model, dtype),
            "xattn": attn_mod.attention_init(
                k2, cfg.d_model, cfg.n_heads, cfg.n_heads, cfg.head_dim,
                dtype=dtype),
            "ln2": rms_norm_init(cfg.d_model, dtype),
            "mlp": gelu_mlp_init(k3, cfg.d_model, cfg.d_ff, dtype=dtype),
        }
    raise ValueError(f"unknown family {fam}")


def init_params(cfg: ArchConfig, key, dtype=None) -> Dict[str, Any]:
    dt = _dtype(cfg, dtype)
    k_emb, k_units, k_extra, k_head = jax.random.split(key, 4)
    params: Dict[str, Any] = {
        "embed": embed_init(k_emb, cfg.vocab, cfg.d_model, dtype=dt),
        "units": _stack(k_units, cfg.n_units,
                        lambda k: _unit_init(k, cfg, dt)),
        "ln_f": rms_norm_init(cfg.d_model, dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = linear_init(k_head, cfg.d_model, cfg.vocab,
                                        dtype=dt)
    if cfg.family == "hybrid" and cfg.attn_every:
        params["shared_attn"] = _attn_block_init(k_extra, cfg, dt)
    if cfg.is_encoder_decoder:
        ke1, ke2 = jax.random.split(k_extra)
        params["encoder"] = {
            "units": _stack(ke1, cfg.encoder_layers,
                            lambda k: _attn_block_init_audio(k, cfg, dt)),
            "ln_f": rms_norm_init(cfg.d_model, dt),
        }
    return params


def _attn_block_init_audio(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": rms_norm_init(cfg.d_model, dtype),
        "attn": attn_mod.attention_init(
            k1, cfg.d_model, cfg.n_heads, cfg.n_heads, cfg.head_dim,
            dtype=dtype),
        "ln2": rms_norm_init(cfg.d_model, dtype),
        "mlp": gelu_mlp_init(k2, cfg.d_model, cfg.d_ff, dtype=dtype),
    }


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ====================================================================== #
# position tables
# ====================================================================== #
def _rope_tables(cfg: ArchConfig, positions: jnp.ndarray):
    """positions: (S,) or (B, S). Returns (cos, sin) or (None, None)."""
    if not cfg.rope:
        return None, None
    if cfg.mrope_sections:
        pos3 = _mrope_positions(cfg, positions)
        return mrope_cos_sin(pos3, cfg.head_dim, cfg.rope_theta,
                             cfg.mrope_sections)
    return rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)


def _mrope_positions(cfg: ArchConfig, positions: jnp.ndarray):
    """Qwen2-VL M-RoPE streams: text tokens use equal t/h/w; the stubbed
    vision prefix gets a (t=0, h, w) grid of width 32."""
    if positions.ndim == 1:
        positions = positions[None]
    tv = cfg.vision_tokens
    grid_w = 32
    is_vis = positions < tv
    h = jnp.where(is_vis, positions // grid_w, positions)
    w = jnp.where(is_vis, positions % grid_w, positions)
    t = jnp.where(is_vis, jnp.zeros_like(positions), positions)
    return jnp.stack([t, h, w])         # (3, B, S)


# ====================================================================== #
# unit forwards (training / prefill)
# ====================================================================== #
def _attn_block_fwd(p, cfg, x, cos, sin, window, use_kernels):
    h = attn_mod.attention(
        p["attn"], rms_norm(p["ln1"], x, cfg.norm_eps), cos, sin,
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, window=window, use_kernel=use_kernels)
    x = x + h
    x = x + swiglu(p["mlp"], rms_norm(p["ln2"], x, cfg.norm_eps))
    return constrain(x, "act_btd")


def _moe_layer_fwd(p, cfg, x, cos, sin, window, use_kernels):
    h = attn_mod.attention(
        p["attn"], rms_norm(p["ln1"], x, cfg.norm_eps), cos, sin,
        n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, window=window, use_kernel=use_kernels)
    x = x + h
    y, aux = moe_mod.moe_forward(
        p["ffn"], rms_norm(p["ln2"], x, cfg.norm_eps),
        n_experts=cfg.moe_experts, top_k=cfg.moe_top_k,
        capacity_factor=cfg.moe_capacity_factor,
        dispatch=cfg.moe_dispatch)
    return constrain(x + y, "act_btd"), aux


def _make_unit_fwd(cfg: ArchConfig, shared_attn, cos, sin, window,
                   use_kernels):
    fam = cfg.family

    if fam in ("dense", "vlm"):
        def unit_fwd(x, p):
            return _attn_block_fwd(p, cfg, x, cos, sin, window,
                                   use_kernels), jnp.zeros(())
    elif fam == "moe":
        u = cfg.pattern_unit()

        def unit_fwd(x, p):
            aux = jnp.zeros(())
            for i in range(u):
                sub = p[f"sub{i}"]
                if i == u - 1:
                    x, a = _moe_layer_fwd(sub, cfg, x, cos, sin, window,
                                          use_kernels)
                    aux = aux + a
                else:
                    x = _attn_block_fwd(sub, cfg, x, cos, sin, window,
                                        use_kernels)
            return x, aux
    elif fam == "hybrid":
        def unit_fwd(x, p):
            def layer(xc, lp):
                h = ssm_mod.mamba2_forward(
                    lp["mamba"], rms_norm(lp["ln"], xc, cfg.norm_eps),
                    d_inner=cfg.d_inner, ssm_state=cfg.ssm_state,
                    n_heads=cfg.n_ssm_heads, use_kernel=use_kernels)
                return constrain(xc + h, "act_btd"), None
            x, _ = jax.lax.scan(layer, x, p["mamba"])
            if shared_attn is not None:
                x = _attn_block_fwd(shared_attn, cfg, x, cos, sin, window,
                                    use_kernels)
            return x, jnp.zeros(())
    elif fam == "ssm":
        def unit_fwd(x, p):
            def layer(xc, lp):
                h = xlstm_mod.mlstm_forward(
                    lp["mlstm"], rms_norm(lp["ln"], xc, cfg.norm_eps),
                    d_inner=cfg.d_inner, n_heads=cfg.n_heads)
                return constrain(xc + h, "act_btd"), None
            x, _ = jax.lax.scan(layer, x, p["mlstm"])
            if "slstm" in p:
                h = xlstm_mod.slstm_forward(
                    p["slstm"]["slstm"],
                    rms_norm(p["slstm"]["ln"], x, cfg.norm_eps),
                    n_heads=cfg.n_heads)
                x = constrain(x + h, "act_btd")
            return x, jnp.zeros(())
    else:
        raise ValueError(fam)
    return unit_fwd


def _scan_units(x, units_params, unit_fwd, remat: bool):
    f = jax.checkpoint(unit_fwd) if remat else unit_fwd

    def body(carry, p):
        x, aux = carry
        x, a = f(x, p)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros(())), units_params)
    return x, aux


# ====================================================================== #
# full forward
# ====================================================================== #
def forward(cfg: ArchConfig, params, batch: Dict[str, jnp.ndarray], *,
            remat: bool = True, use_kernels: bool = False
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """batch: {"tokens": (B,S) i32 [, "vision_embeds" (B,Tv,D),
    "frames" (B,Senc,D)]} -> (logits (B,S,V), aux)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    dt = _dtype(cfg)
    x = embed(params["embed"], tokens, dt)
    if cfg.family == "vlm" and "vision_embeds" in batch:
        tv = batch["vision_embeds"].shape[1]
        x = jnp.concatenate(
            [batch["vision_embeds"].astype(dt), x[:, tv:]], axis=1)
    x = constrain(x, "act_btd")

    if cfg.is_encoder_decoder:
        enc = encode(cfg, params, batch["frames"], remat=remat)
        return _decoder_forward(cfg, params, x, enc, remat=remat)

    positions = jnp.arange(s)
    cos, sin = _rope_tables(cfg, positions)
    if cfg.family == "ssm" and not cfg.rope:
        cos = sin = None
    shared = params.get("shared_attn")
    unit_fwd = _make_unit_fwd(cfg, shared, cos, sin, cfg.sliding_window,
                              use_kernels)
    x, aux = _scan_units(x, params["units"], unit_fwd, remat)
    x = rms_norm(params["ln_f"], x, cfg.norm_eps)
    logits = _lm_head(cfg, params, x)
    return constrain(logits, "logits"), aux


def _lm_head(cfg, params, x):
    if cfg.tie_embeddings:
        return unembed(params["embed"], x)
    return linear(params["lm_head"], x)


# ---------------------------------------------------------------------- #
# whisper encoder / decoder
# ---------------------------------------------------------------------- #
def encode(cfg: ArchConfig, params, frames, *, remat: bool = True):
    """frames: (B, Senc, D) stubbed conv-frontend output."""
    dt = _dtype(cfg)
    b, s, _ = frames.shape
    pos = sinusoidal_positions(jnp.arange(s), cfg.d_model).astype(dt)
    x = frames.astype(dt) + pos[None]

    def unit_fwd(x, p):
        h = attn_mod.attention(
            p["attn"], rms_norm(p["ln1"], x, cfg.norm_eps), None, None,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_heads,
            head_dim=cfg.head_dim, causal=False)
        x = x + h
        x = x + gelu_mlp(p["mlp"], rms_norm(p["ln2"], x, cfg.norm_eps))
        return constrain(x, "act_btd"), jnp.zeros(())

    x, _ = _scan_units(x, params["encoder"]["units"], unit_fwd, remat)
    return rms_norm(params["encoder"]["ln_f"], x, cfg.norm_eps)


def _decoder_forward(cfg, params, x, enc, *, remat: bool):
    b, s, _ = x.shape
    dt = x.dtype
    pos = sinusoidal_positions(jnp.arange(s), cfg.d_model).astype(dt)
    x = x + pos[None]

    def unit_fwd(x, p):
        h = attn_mod.attention(
            p["attn"], rms_norm(p["ln1"], x, cfg.norm_eps), None, None,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_heads,
            head_dim=cfg.head_dim, causal=True)
        x = x + h
        # cross attention over encoder output
        xq = rms_norm(p["lnx"], x, cfg.norm_eps)
        h = _cross_attention(p["xattn"], cfg, xq, enc)
        x = x + h
        x = x + gelu_mlp(p["mlp"], rms_norm(p["ln2"], x, cfg.norm_eps))
        return constrain(x, "act_btd"), jnp.zeros(())

    x, aux = _scan_units(x, params["units"], unit_fwd, remat)
    x = rms_norm(params["ln_f"], x, cfg.norm_eps)
    return _lm_head(cfg, params, x), aux


def _cross_attention(p, cfg, xq, enc):
    b, s, _ = xq.shape
    se = enc.shape[1]
    hd, nh = cfg.head_dim, cfg.n_heads
    q = linear(p["wq"], xq).reshape(b, s, nh, hd)
    k = linear(p["wk"], enc).reshape(b, se, nh, hd)
    v = linear(p["wv"], enc).reshape(b, se, nh, hd)
    out = attn_mod.full_attention(q, k, v, causal=False)
    return linear(p["wo"], out.reshape(b, s, nh * hd))


# ====================================================================== #
# decode (serving)
# ====================================================================== #
def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               dtype=None) -> Any:
    """Zeroed decode cache pytree (stacked over units). ``max_len`` is the
    KV-cache length; sliding-window archs allocate min(window, max_len)."""
    dt = _dtype(cfg, dtype)
    fam = cfg.family
    win = cfg.sliding_window
    kv_len = min(win, max_len) if win else max_len

    def kv(h_kv):
        return {"k": jnp.zeros((batch, kv_len, h_kv, cfg.head_dim), dt),
                "v": jnp.zeros((batch, kv_len, h_kv, cfg.head_dim), dt)}

    def unit_cache():
        if fam in ("dense", "vlm"):
            return kv(cfg.n_kv_heads)
        if fam == "moe":
            return {f"sub{i}": kv(cfg.n_kv_heads)
                    for i in range(cfg.pattern_unit())}
        if fam == "hybrid":
            u = cfg.pattern_unit()
            m = ssm_mod.mamba2_init_cache(
                batch, cfg.d_inner, cfg.ssm_state, cfg.n_ssm_heads,
                cfg.ssm_conv, dt)
            return {"mamba": jax.tree.map(
                lambda t: jnp.broadcast_to(t, (u,) + t.shape), m),
                "shared": kv(cfg.n_kv_heads)}
        if fam == "ssm":
            u = cfg.pattern_unit()
            mc = xlstm_mod.mlstm_init_cache(batch, cfg.d_inner, cfg.n_heads)
            cache = {"mlstm": jax.tree.map(
                lambda t: jnp.broadcast_to(t, (max(1, u - 1),) + t.shape),
                mc)}
            if cfg.slstm_every:
                cache["slstm"] = xlstm_mod.slstm_init_cache(
                    batch, cfg.d_model)
            return cache
        if fam == "audio":
            cross = {"k": jnp.zeros((batch, cfg.encoder_seq, cfg.n_heads,
                                     cfg.head_dim), dt),
                     "v": jnp.zeros((batch, cfg.encoder_seq, cfg.n_heads,
                                     cfg.head_dim), dt)}
            return {"self": kv(cfg.n_heads), "cross": cross}
        raise ValueError(fam)

    units = jax.tree.map(
        lambda t: jnp.broadcast_to(t, (cfg.n_units,) + t.shape),
        unit_cache())
    return {"units": units, "index": jnp.zeros((), jnp.int32)}


def init_paged_cache(cfg: ArchConfig, batch: int, max_len: int, *,
                     page_size: int, n_pages: int, dtype=None) -> Any:
    """Paged decode cache (DESIGN.md §15): every *linear-layout* KV leaf
    — the ``{"k","v"}`` caches that ``init_cache`` allocates densely as
    ``(batch, max_len, Hkv, D)`` — becomes a shared pool
    ``(n_pages, page_size, Hkv*D)`` (kv heads flattened into the minor
    dim, which the TPU tiles without padding) addressed through one
    top-level
    block table ``cache["pages"]: (batch, max_len // page_size) i32``
    (-1 = unassigned).  One table serves every attention leaf because all
    of them write the same row position each step.  Non-attention state
    (SSM, conv, mLSTM) and non-linear layouts (sliding-window rings,
    whisper cross K/V) keep their dense per-slot allocation — they are
    O(1) per slot, not O(max_len).

    ``max_len % page_size == 0`` is required: the jnp read path gathers
    the table into a ``(batch, P * page_size, ...)`` view whose shape
    must equal the dense cache for bitwise token identity."""
    assert max_len % page_size == 0, (max_len, page_size)
    assert cfg.sliding_window == 0, \
        "paged KV requires the linear cache layout (window == 0)"
    dt = _dtype(cfg, dtype)

    def paged_kv(h_kv):
        shape = (n_pages, page_size, h_kv * cfg.head_dim)
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

    fam = cfg.family
    if fam in ("dense", "vlm"):
        unit = paged_kv(cfg.n_kv_heads)
    elif fam == "moe":
        unit = {f"sub{i}": paged_kv(cfg.n_kv_heads)
                for i in range(cfg.pattern_unit())}
    elif fam == "hybrid":
        u = cfg.pattern_unit()
        m = ssm_mod.mamba2_init_cache(
            batch, cfg.d_inner, cfg.ssm_state, cfg.n_ssm_heads,
            cfg.ssm_conv, dt)
        unit = {"mamba": jax.tree.map(
            lambda t: jnp.broadcast_to(t, (u,) + t.shape), m),
            "shared": paged_kv(cfg.n_kv_heads)}
    elif fam == "audio":
        cross = {"k": jnp.zeros((batch, cfg.encoder_seq, cfg.n_heads,
                                 cfg.head_dim), dt),
                 "v": jnp.zeros((batch, cfg.encoder_seq, cfg.n_heads,
                                 cfg.head_dim), dt)}
        unit = {"self": paged_kv(cfg.n_heads), "cross": cross}
    else:
        raise ValueError(f"family {fam!r} has no linear KV cache to page")

    units = jax.tree.map(
        lambda t: jnp.broadcast_to(t, (cfg.n_units,) + t.shape), unit)
    return {"units": units, "index": jnp.zeros((), jnp.int32),
            "pages": jnp.full((batch, max_len // page_size), -1,
                              jnp.int32)}


def prefill_cache_whisper(cfg, params, frames, batch, max_len, dtype=None):
    """Whisper: run the encoder once, precompute per-layer cross K/V."""
    cache = init_cache(cfg, batch, max_len, dtype)
    enc = encode(cfg, params, frames, remat=False)
    b, se, _ = enc.shape

    def per_unit(p):
        k = linear(p["xattn"]["wk"], enc).reshape(
            b, se, cfg.n_heads, cfg.head_dim)
        v = linear(p["xattn"]["wv"], enc).reshape(
            b, se, cfg.n_heads, cfg.head_dim)
        return k, v

    ks, vs = jax.vmap(per_unit)(params["units"])    # (U, B, Se, H, D)
    cross = cache["units"]["cross"]
    pad = cross["k"].shape[2] - ks.shape[2]
    if pad >= 0:
        ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    else:
        ks, vs = ks[:, :, :cross["k"].shape[2]], vs[:, :, :cross["k"].shape[2]]
    cache["units"]["cross"] = {"k": ks.astype(cross["k"].dtype),
                               "v": vs.astype(cross["v"].dtype)}
    cache["cross_len"] = jnp.asarray(min(se, cross["k"].shape[2]), jnp.int32)
    return cache


def prefill(cfg: ArchConfig, params, cache, tokens, *,
            use_kernels: bool = False) -> Tuple[jnp.ndarray, Any]:
    """Single-shot prefill: populate a FRESH decode cache (index 0) from
    the whole prompt in ONE call instead of S sequential ``decode_step``
    dispatches.  tokens: (B, S) i32; for whisper, ``cache`` comes from
    ``prefill_cache_whisper`` (cross K/V already populated).

    Returns (logits (B, S, V), cache): the logits match teacher-forced
    ``forward`` position by position, and the cache is the one a
    per-token decode_step loop would have produced (KV rows / ring slots
    / SSM, conv, mLSTM, sLSTM states), with ``index`` advanced to S."""
    dt = _dtype(cfg)
    b, s = tokens.shape
    fam = cfg.family
    win = cfg.sliding_window
    x = embed(params["embed"], tokens, dt)
    cos = sin = None
    if cfg.is_encoder_decoder:
        pos = sinusoidal_positions(jnp.arange(s), cfg.d_model).astype(dt)
        x = x + pos[None]
    else:
        cos, sin = _rope_tables(cfg, jnp.arange(s))

    shared = params.get("shared_attn")
    akw = dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
               head_dim=cfg.head_dim, window=win, use_kernel=use_kernels)

    def unit_prefill(x, p, c):
        new_c = c
        if fam in ("dense", "vlm"):
            h, kv = attn_mod.attention_prefill(
                p["attn"], rms_norm(p["ln1"], x, cfg.norm_eps),
                cos, sin, c, **akw)
            x = x + h
            x = x + swiglu(p["mlp"], rms_norm(p["ln2"], x, cfg.norm_eps))
            new_c = kv
        elif fam == "moe":
            new_c = dict(c)
            u = cfg.pattern_unit()
            for i in range(u):
                sub = p[f"sub{i}"]
                h, kv = attn_mod.attention_prefill(
                    sub["attn"], rms_norm(sub["ln1"], x, cfg.norm_eps),
                    cos, sin, c[f"sub{i}"], **akw)
                x = x + h
                hn = rms_norm(sub["ln2"], x, cfg.norm_eps)
                if i == u - 1:
                    y, _ = moe_mod.moe_forward(
                        sub["ffn"], hn, n_experts=cfg.moe_experts,
                        top_k=cfg.moe_top_k,
                        capacity_factor=cfg.moe_capacity_factor,
                        dispatch=cfg.moe_dispatch)
                else:
                    y = swiglu(sub["mlp"], hn)
                x = x + y
                new_c[f"sub{i}"] = kv
        elif fam == "hybrid":
            def layer(carry, pc):
                xc = carry
                lp, lc = pc
                h, nc = ssm_mod.mamba2_prefill(
                    lp["mamba"], rms_norm(lp["ln"], xc, cfg.norm_eps),
                    lc, d_inner=cfg.d_inner, ssm_state=cfg.ssm_state,
                    n_heads=cfg.n_ssm_heads)
                return xc + h, nc
            x, new_mamba = jax.lax.scan(layer, x, (p["mamba"], c["mamba"]))
            new_c = {"mamba": new_mamba, "shared": c["shared"]}
            if shared is not None:
                h, kv = attn_mod.attention_prefill(
                    shared["attn"], rms_norm(shared["ln1"], x, cfg.norm_eps),
                    cos, sin, c["shared"], **akw)
                x = x + h
                x = x + swiglu(shared["mlp"],
                               rms_norm(shared["ln2"], x, cfg.norm_eps))
                new_c["shared"] = kv
        elif fam == "ssm":
            def layer(carry, pc):
                xc = carry
                lp, lc = pc
                h, nc = xlstm_mod.mlstm_prefill(
                    lp["mlstm"], rms_norm(lp["ln"], xc, cfg.norm_eps),
                    lc, d_inner=cfg.d_inner, n_heads=cfg.n_heads)
                return xc + h, nc
            x, new_m = jax.lax.scan(layer, x, (p["mlstm"], c["mlstm"]))
            new_c = {"mlstm": new_m}
            if "slstm" in p:
                # slstm_decode scans any S — it doubles as the prefill
                h, nc = xlstm_mod.slstm_decode(
                    p["slstm"]["slstm"],
                    rms_norm(p["slstm"]["ln"], x, cfg.norm_eps),
                    c["slstm"], n_heads=cfg.n_heads)
                x = x + h
                new_c["slstm"] = nc
        elif fam == "audio":
            h, kv = attn_mod.attention_prefill(
                p["attn"], rms_norm(p["ln1"], x, cfg.norm_eps),
                None, None, c["self"],
                n_heads=cfg.n_heads, n_kv_heads=cfg.n_heads,
                head_dim=cfg.head_dim, window=0, use_kernel=use_kernels)
            x = x + h
            xq = rms_norm(p["lnx"], x, cfg.norm_eps)
            h = _cross_attention_cached(p["xattn"], cfg, xq, c["cross"],
                                        cache.get("cross_len"))
            x = x + h
            x = x + gelu_mlp(p["mlp"], rms_norm(p["ln2"], x, cfg.norm_eps))
            new_c = {"self": kv, "cross": c["cross"]}
        else:
            raise ValueError(fam)
        return x, new_c

    def body(x, pc):
        p, c = pc
        return unit_prefill(x, p, c)

    x = constrain(x, "act_btd")
    x, new_units = jax.lax.scan(body, x, (params["units"], cache["units"]))
    x = rms_norm(params["ln_f"], x, cfg.norm_eps)
    logits = _lm_head(cfg, params, x)
    new_cache = dict(cache)
    new_cache["units"] = new_units
    new_cache["index"] = jnp.full_like(cache["index"], s)
    return constrain(logits, "logits"), new_cache


def prefill_extend(cfg: ArchConfig, params, cache, tokens, *,
                   start: int, use_kernels: bool = False
                   ) -> Tuple[jnp.ndarray, Any]:
    """Suffix prefill (DESIGN.md §18): continue a cache whose rows
    ``[0, start)`` are already populated — the prefix-shared serving path
    gathers a request's matched prompt prefix out of the page pool and
    computes only the un-cached suffix here.  tokens: (B, S_suffix) i32
    at absolute positions ``start .. start+S-1``.

    Families with position-local per-layer state only (dense / vlm /
    moe): attention is the sole cross-position op, so every suffix row's
    hidden state — and therefore the K/V rows and logits — is BITWISE
    identical to the same rows of a full ``prefill`` (suffix >= 2 rows;
    see ``attention_prefill_extend``).  SSM/conv state (hybrid, ssm)
    would need a snapshot at ``start`` and is rejected.  MoE caveat: the
    router's capacity semantics see only the suffix tokens, mirroring
    the one-shot-prefill caveat in ``serve.generate`` — at generous
    capacity factors (no drops) routing is per-token and identity holds.

    Returns (logits (B, S_suffix, V), cache with index start+S)."""
    assert cfg.family in ("dense", "vlm", "moe"), \
        f"prefill_extend requires position-local state; family " \
        f"{cfg.family!r} carries recurrent state across positions"
    assert cfg.sliding_window == 0, "linear cache layout only"
    dt = _dtype(cfg)
    b, s = tokens.shape
    fam = cfg.family
    x = embed(params["embed"], tokens, dt)
    cos, sin = _rope_tables(cfg, jnp.arange(start, start + s))
    akw = dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
               head_dim=cfg.head_dim, start=start, use_kernel=use_kernels)

    def unit_extend(x, p, c):
        if fam in ("dense", "vlm"):
            h, kv = attn_mod.attention_prefill_extend(
                p["attn"], rms_norm(p["ln1"], x, cfg.norm_eps),
                cos, sin, c, **akw)
            x = x + h
            x = x + swiglu(p["mlp"], rms_norm(p["ln2"], x, cfg.norm_eps))
            return x, kv
        new_c = dict(c)                                     # moe
        u = cfg.pattern_unit()
        for i in range(u):
            sub = p[f"sub{i}"]
            h, kv = attn_mod.attention_prefill_extend(
                sub["attn"], rms_norm(sub["ln1"], x, cfg.norm_eps),
                cos, sin, c[f"sub{i}"], **akw)
            x = x + h
            hn = rms_norm(sub["ln2"], x, cfg.norm_eps)
            if i == u - 1:
                y, _ = moe_mod.moe_forward(
                    sub["ffn"], hn, n_experts=cfg.moe_experts,
                    top_k=cfg.moe_top_k,
                    capacity_factor=cfg.moe_capacity_factor,
                    dispatch=cfg.moe_dispatch)
            else:
                y = swiglu(sub["mlp"], hn)
            x = x + y
            new_c[f"sub{i}"] = kv
        return x, new_c

    def body(x, pc):
        p, c = pc
        return unit_extend(x, p, c)

    x = constrain(x, "act_btd")
    x, new_units = jax.lax.scan(body, x, (params["units"], cache["units"]))
    x = rms_norm(params["ln_f"], x, cfg.norm_eps)
    logits = _lm_head(cfg, params, x)
    new_cache = dict(cache)
    new_cache["units"] = new_units
    new_cache["index"] = jnp.full_like(cache["index"], start + s)
    return constrain(logits, "logits"), new_cache


def decode_step(cfg: ArchConfig, params, cache, tokens, *,
                index=None, use_kernels: bool = False
                ) -> Tuple[jnp.ndarray, Any]:
    """tokens: (B, 1) i32; index: absolute position, scalar or per-example
    (B,) vector (defaults to cache['index']). Returns (logits (B,1,V),
    new cache).  ``use_kernels=True`` routes linear-layout KV attention
    through the Pallas flash-decode kernel."""
    dt = _dtype(cfg)
    b = tokens.shape[0]
    idx = cache["index"] if index is None else jnp.asarray(index)
    x = embed(params["embed"], tokens, dt)
    fam = cfg.family
    win = cfg.sliding_window

    if cfg.is_encoder_decoder:
        pos = sinusoidal_positions(idx if idx.ndim else idx[None],
                                   cfg.d_model).astype(dt)
        x = x + pos[:, None]                     # (B or 1, 1, D)
    else:
        positions = idx[:, None] if idx.ndim else idx[None][None]
        cos, sin = _rope_tables(cfg, positions)  # (B or 1, S=1) positions
        if cos is not None and cos.shape[0] == 1:
            cos = jnp.broadcast_to(cos, (b,) + cos.shape[1:])
            sin = jnp.broadcast_to(sin, (b,) + sin.shape[1:])

    shared = params.get("shared_attn")
    pages = cache.get("pages")        # paged KV block table (B, P) or None
    akw = dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
               head_dim=cfg.head_dim, window=win, use_kernel=use_kernels,
               pages=pages)

    def unit_step(x, p, c):
        new_c = c
        if fam in ("dense", "vlm"):
            h, kv = attn_mod.attention_decode(
                p["attn"], rms_norm(p["ln1"], x, cfg.norm_eps),
                cos, sin, c, idx, **akw)
            x = x + h
            x = x + swiglu(p["mlp"], rms_norm(p["ln2"], x, cfg.norm_eps))
            new_c = kv
        elif fam == "moe":
            new_c = dict(c)
            u = cfg.pattern_unit()
            for i in range(u):
                sub = p[f"sub{i}"]
                h, kv = attn_mod.attention_decode(
                    sub["attn"], rms_norm(sub["ln1"], x, cfg.norm_eps),
                    cos, sin, c[f"sub{i}"], idx, **akw)
                x = x + h
                hn = rms_norm(sub["ln2"], x, cfg.norm_eps)
                if i == u - 1:
                    y, _ = moe_mod.moe_forward(
                        sub["ffn"], hn, n_experts=cfg.moe_experts,
                        top_k=cfg.moe_top_k,
                        capacity_factor=cfg.moe_capacity_factor,
                        dispatch=cfg.moe_dispatch)
                else:
                    y = swiglu(sub["mlp"], hn)
                x = x + y
                new_c[f"sub{i}"] = kv
        elif fam == "hybrid":
            def layer(carry, pc):
                xc = carry
                lp, lc = pc
                h, nc = ssm_mod.mamba2_decode(
                    lp["mamba"], rms_norm(lp["ln"], xc, cfg.norm_eps),
                    lc, d_inner=cfg.d_inner, ssm_state=cfg.ssm_state,
                    n_heads=cfg.n_ssm_heads)
                return xc + h, nc
            x, new_mamba = jax.lax.scan(layer, x, (p["mamba"], c["mamba"]))
            new_c = {"mamba": new_mamba, "shared": c["shared"]}
            if shared is not None:
                h, kv = attn_mod.attention_decode(
                    shared["attn"], rms_norm(shared["ln1"], x, cfg.norm_eps),
                    cos, sin, c["shared"], idx, **akw)
                x = x + h
                x = x + swiglu(shared["mlp"],
                               rms_norm(shared["ln2"], x, cfg.norm_eps))
                new_c["shared"] = kv
        elif fam == "ssm":
            def layer(carry, pc):
                xc = carry
                lp, lc = pc
                h, nc = xlstm_mod.mlstm_decode(
                    lp["mlstm"], rms_norm(lp["ln"], xc, cfg.norm_eps),
                    lc, d_inner=cfg.d_inner, n_heads=cfg.n_heads)
                return xc + h, nc
            x, new_m = jax.lax.scan(layer, x, (p["mlstm"], c["mlstm"]))
            new_c = {"mlstm": new_m}
            if "slstm" in p:
                h, nc = xlstm_mod.slstm_decode(
                    p["slstm"]["slstm"],
                    rms_norm(p["slstm"]["ln"], x, cfg.norm_eps),
                    c["slstm"], n_heads=cfg.n_heads)
                x = x + h
                new_c["slstm"] = nc
        elif fam == "audio":
            h, kv = attn_mod.attention_decode(
                p["attn"], rms_norm(p["ln1"], x, cfg.norm_eps),
                None, None, c["self"], idx,
                n_heads=cfg.n_heads, n_kv_heads=cfg.n_heads,
                head_dim=cfg.head_dim, window=0, use_kernel=use_kernels,
                pages=pages)
            x = x + h
            xq = rms_norm(p["lnx"], x, cfg.norm_eps)
            h = _cross_decode(p["xattn"], cfg, xq, c["cross"],
                              cache.get("cross_len"))
            x = x + h
            x = x + gelu_mlp(p["mlp"], rms_norm(p["ln2"], x, cfg.norm_eps))
            new_c = {"self": kv, "cross": c["cross"]}
        else:
            raise ValueError(fam)
        return x, new_c

    def body(x, pc):
        p, c = pc
        return unit_step(x, p, c)

    x = constrain(x, "act_btd")
    x, new_units = jax.lax.scan(body, x, (params["units"], cache["units"]))
    x = rms_norm(params["ln_f"], x, cfg.norm_eps)
    logits = _lm_head(cfg, params, x)
    new_cache = dict(cache)
    new_cache["units"] = new_units
    new_cache["index"] = idx + 1
    return constrain(logits, "logits"), new_cache


def _cross_attention_cached(p, cfg, xq, cross, cross_len):
    """Cross attention of S query positions against cached (padded)
    encoder K/V, masked to the ``cross_len`` valid prefix."""
    b, s, _ = xq.shape
    hd, nh = cfg.head_dim, cfg.n_heads
    q = linear(p["wq"], xq).reshape(b, s, nh, hd)
    k, v = cross["k"], cross["v"]
    scale = hd ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if cross_len is not None:
        valid = jnp.arange(k.shape[1])[None, :] < cross_len
        scores = jnp.where(valid[:, None, None, :], scores, attn_mod.NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return linear(p["wo"], out.astype(xq.dtype).reshape(b, s, nh * hd))


def _cross_decode(p, cfg, xq, cross, cross_len):
    return _cross_attention_cached(p, cfg, xq, cross, cross_len)
