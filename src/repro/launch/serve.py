"""Fused-decode serving driver.

Prefill populates the per-family cache (KV / ring-buffer / SSM state)
with ONE full-sequence jitted call (``models.prefill``), and generation
runs the whole token loop inside one jitted ``lax.scan`` program —
an N-token generation is one dispatch instead of N, with the cache
buffers donated to the scan.  The seed's per-token paths are kept as
``prefill_mode="per_token"`` / ``engine="eager"`` benchmark baselines.

Jitted callables are cached at module level across ``generate()`` calls,
keyed by config identity + batch/sequence shape, so repeated calls (a
serving loop, the benchmark) never re-trace.

``python -m repro.launch.serve --arch xlstm-1.3b --reduced --tokens 32``
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_NAMES, get_config
from repro.util.compile_cache import enable_compile_cache
from repro.util.spans import named
from repro.models import (decode_step, init_cache, init_params, prefill,
                          prefill_cache_whisper, prefill_extend)

# jitted decode/prefill callables, reused across generate() calls
_JIT_CACHE: Dict[tuple, Callable] = {}


def _cached(key: tuple, make: Callable[[], Callable]) -> Callable:
    fn = _JIT_CACHE.get(key)
    if fn is None:
        fn = _JIT_CACHE[key] = make()
    return fn


def jit_cache_size() -> int:
    return len(_JIT_CACHE)


def clear_jit_cache() -> None:
    _JIT_CACHE.clear()


# ---------------------------------------------------------------------- #
# prefill
# ---------------------------------------------------------------------- #
def _decode_step_fn(cfg, use_kernels: bool) -> Callable:
    """Cache keys hold only trace-affecting Python values; jax.jit keys
    the input shapes itself, so the dict stays bounded per config."""
    return _cached(("step", cfg, use_kernels), lambda: jax.jit(named(
        "decode_step", lambda p, c, t: decode_step(
            cfg, p, c, t, use_kernels=use_kernels))))


def prefill_one_shot(cfg, params, tokens, cache, *,
                     use_kernels: bool = False):
    """Single-shot prefill: one jitted call populates the whole cache.
    Returns (last-position logits (B, 1, V), cache)."""
    fn = _cached(("prefill", cfg, use_kernels),
                 lambda: jax.jit(named("prefill", lambda p, c, t: prefill(
                     cfg, p, c, t, use_kernels=use_kernels))))
    logits, cache = fn(params, cache, tokens)
    return logits[:, -1:], cache


def prefill_extend_cached(cfg, params, cache, tokens, *, start: int,
                          use_kernels: bool = False):
    """Suffix prefill (prefix-shared serving, DESIGN.md §18): one jitted
    call computes rows ``[start, start+S)`` into a cache whose prefix
    rows are already populated.  ``start`` is a static Python int — it
    keys the cache entry (and the trace) so the sliced attention extent
    stays exact, which the bitwise-identity contract requires.  Returns
    (logits (B, S, V), cache)."""
    fn = _cached(("prefill_extend", cfg, start, use_kernels),
                 lambda: jax.jit(named(
                     "prefill_extend", lambda p, c, t: prefill_extend(
                         cfg, p, c, t, start=start,
                         use_kernels=use_kernels))))
    return fn(params, cache, tokens)


def prefill_per_token(cfg, params, tokens, cache, *,
                      use_kernels: bool = False):
    """Seed-style teacher-forced prefill: T sequential ``decode_step``
    dispatches (kept as the benchmark baseline)."""
    step = _decode_step_fn(cfg, use_kernels)
    for t in range(tokens.shape[1]):
        logits, cache = step(params, cache, tokens[:, t:t + 1])
    return logits[:, -1:], cache


# ---------------------------------------------------------------------- #
# generation
# ---------------------------------------------------------------------- #
def _make_scan_generate(cfg, steps: int, greedy: bool, use_kernels: bool):
    """The fused loop: token scan inside one jitted program.  Emits the
    carried token each step and samples the next from its logits — the
    exact op/key order of the eager loop, so outputs are bit-identical.
    Returns (tokens (B, steps), cache, next token, key) so callers that
    segment generation (``launch/engine.py``) can continue the carry."""
    def decode_segment(params, cache, tok, key):
        def body(carry, _):
            cache, tok, key = carry
            logits, cache = decode_step(cfg, params, cache, tok,
                                        use_kernels=use_kernels)
            if greedy:
                nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            else:
                key, sub = jax.random.split(key)
                nxt = jax.random.categorical(
                    sub, logits[:, -1])[:, None].astype(jnp.int32)
            return (cache, nxt, key), tok
        (cache, tok, key), toks = jax.lax.scan(
            body, (cache, tok, key), length=steps)
        return jnp.moveaxis(toks[:, :, 0], 0, 1), cache, tok, key
    return decode_segment


def generate(cfg, params, prompt, *, max_new_tokens=16, max_len=256,
             greedy=True, frames=None, key=None, engine="scan",
             prefill_mode="one_shot", use_kernels=False):
    """Generate ``max_new_tokens`` tokens for a (B, S) prompt batch.

    engine: "scan" (fused lax.scan loop, one dispatch) or "eager"
    (per-token dispatches, the seed path).  prefill_mode: "one_shot"
    (one jitted call) or "per_token".  Both pairs produce identical
    tokens, with one caveat: one-shot prefill routes MoE prompts through
    the batched ``forward`` capacity semantics, so at tight
    ``moe_capacity_factor`` a saturated expert may drop prompt tokens
    the per-token path would route — pass ``prefill_mode="per_token"``
    or raise the capacity factor for exact parity on MoE archs."""
    b = prompt.shape[0]
    if key is None:
        key = jax.random.PRNGKey(0)
    if cfg.is_encoder_decoder:
        assert frames is not None
        cache = prefill_cache_whisper(cfg, params, frames, b, max_len)
    else:
        cache = init_cache(cfg, b, max_len)

    if prefill_mode == "one_shot":
        logits, cache = prefill_one_shot(cfg, params, prompt, cache,
                                         use_kernels=use_kernels)
    elif prefill_mode == "per_token":
        logits, cache = prefill_per_token(cfg, params, prompt, cache,
                                          use_kernels=use_kernels)
    else:
        raise ValueError(f"unknown prefill_mode {prefill_mode!r}")
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)

    if engine == "scan":
        run = _cached(
            ("generate", cfg, max_new_tokens, greedy, use_kernels),
            lambda: jax.jit(_make_scan_generate(
                cfg, max_new_tokens, greedy, use_kernels),
                donate_argnums=(1,)))          # cache buffers are donated
        toks = run(params, cache, tok, key)[0]
        return toks
    if engine == "eager":
        step = _decode_step_fn(cfg, use_kernels)
        out = []
        for _ in range(max_new_tokens):
            out.append(tok)
            logits, cache = step(params, cache, tok)
            if greedy:
                tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            else:
                key, sub = jax.random.split(key)
                tok = jax.random.categorical(
                    sub, logits[:, -1])[:, None].astype(jnp.int32)
        return jnp.concatenate(out, axis=1)
    raise ValueError(f"unknown engine {engine!r}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="xlstm-1.3b", choices=ARCH_NAMES)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--engine", default="scan", choices=("scan", "eager"))
    ap.add_argument("--prefill", default="one_shot",
                    choices=("one_shot", "per_token"))
    ap.add_argument("--kernels", action="store_true",
                    help="Pallas flash-decode path (interpret mode on CPU)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)), jnp.int32)
    frames = None
    if cfg.is_encoder_decoder:
        frames = jnp.asarray(rng.standard_normal(
            (args.batch, cfg.encoder_seq, cfg.d_model)) * 0.02, jnp.float32)
    t0 = time.perf_counter()
    toks = generate(cfg, params, prompt, max_new_tokens=args.tokens,
                    frames=frames, engine=args.engine,
                    prefill_mode=args.prefill, use_kernels=args.kernels)
    jax.block_until_ready(toks)
    dt = time.perf_counter() - t0
    print(f"arch={cfg.name} engine={args.engine} generated {toks.shape} "
          f"in {dt:.2f}s ({args.batch * args.tokens / dt:.1f} tok/s)")
    print(np.asarray(toks[0]))
    return toks


if __name__ == "__main__":
    main()
