"""Pages per grid step of the paged decode kernel, swept on the chip.

Times ``flash_decode_paged`` over all of a stacked pool's layers at the
served model's widths (minicpm-2b: 36 MHA heads x 64, a 40-layer pool of
512 x 16-row pages, 8 slots of 2048 positions) for each number of pages
a grid step reads, under two mixes of slot lengths:

* ``cell``: five live slots at lengths like the sessions traffic's, and
  three freed slots whose table rows are -1 and whose stale lengths run
  on (two of them past the table), as the serving engine leaves them;
* ``full``: all eight slots live over the whole table.

Each point is the median of ``--reps`` timed calls of one jitted loop
over the 40 layers (``block_until_ready`` inside the clock), and its
largest distance from the float32 gather oracle at layer 3.  The byte
budget ``kernels.flash_decode.KV_BLOCK_BYTES``, which this script sets
to n pages for each point, is chosen from these numbers; n = 1 is the
grid of one page a step.

Usage (on a TPU; the pool alone takes 6 GB of device memory; on the CPU
the kernel is interpreted and the times mean nothing):
    PYTHONPATH=src python -m benchmarks.decode_kernel_sweep \\
        --pages 1,2,4,8,16,32 --out chiprun_out/decode_sweep.json
"""
from __future__ import annotations

import argparse
import functools
import json
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import flash_decode as fd
from repro.kernels.ref import flash_decode_paged_ref

LAYERS, N_PAGES, PS, H, D, SLOTS, P_TAB = 40, 512, 16, 36, 64, 8, 128

MIXES = {
    # (live lengths, stale lengths of freed slots)
    "cell": ([1290, 420, 760, 1100, 330], [2600, 1500, 3100]),
    "full": ([2048] * SLOTS, []),
}


def _tables(live, stale, rng):
    pages = np.full((SLOTS, P_TAB), -1, np.int32)
    free = list(rng.permutation(N_PAGES))
    for slot, n in enumerate(live):
        for j in range(-(-n // PS)):
            pages[slot, j] = free.pop()
    lengths = np.asarray(list(live) + list(stale), np.int32)
    return jnp.asarray(pages), jnp.asarray(lengths)


def _time(fn, args, reps):
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--pages", default="1,2,4,8,16,32")
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    dev = jax.devices()[0]
    interpret = dev.platform == "cpu"         # a rehearsal: no timings
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    shape = (LAYERS, N_PAGES, PS, H * D)
    pool_k = jax.random.normal(kk, shape, jnp.bfloat16)
    pool_v = jax.random.normal(kv, shape, jnp.bfloat16)
    q = jax.random.normal(kq, (SLOTS, 1, H, D), jnp.bfloat16)
    rng = np.random.default_rng(0)
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "page_bytes": PS * H * D * 2, "points": []}
    for mix, (live, stale) in MIXES.items():
        pages, lengths = _tables(live, stale, rng)
        ref = np.asarray(flash_decode_paged_ref(
            q.astype(jnp.float32), pool_k[3].astype(jnp.float32),
            pool_v[3].astype(jnp.float32), pages, lengths))
        for n in [int(x) for x in args.pages.split(",")]:
            fd.KV_BLOCK_BYTES = n * result["page_bytes"]
            call = functools.partial(fd.flash_decode_paged,
                                     interpret=interpret)

            @jax.jit
            def all_layers(q, pk, pv, pages, lengths):
                def body(layer, acc):
                    out = call(q, pk, pv, pages, lengths, layer)
                    return acc + out.astype(jnp.float32)
                return jax.lax.fori_loop(0, LAYERS, body,
                                         jnp.zeros(q.shape, jnp.float32))

            one = jax.jit(call)(q, pool_k, pool_v, pages, lengths, 3)
            args_ = (q, pool_k, pool_v, pages, lengths)
            sec = _time(all_layers, args_, args.reps)
            err = float(np.max(np.abs(np.asarray(one, np.float32) - ref)))
            point = {"mix": mix, "pages_per_step": n,
                     "k_block_bytes": n * result["page_bytes"],
                     "steps_per_slot": -(-P_TAB // n),
                     "ms_per_call": sec * 1e3,
                     "us_per_layer": sec * 1e6 / LAYERS,
                     "max_abs_err_vs_f32": err}
            result["points"].append(point)
            print(json.dumps(point), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
