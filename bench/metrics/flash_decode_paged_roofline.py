"""The paged flash-decode kernel's share of its roofline (%): the least
time the rows served in the traced window could take to read and
multiply (``bench/costs/flash_decode_paged.py``, every layer, one step
per token received after the first), over the kernel's time in the
device trace."""
from bench.costs import flash_decode_paged as fd
from bench.reference import Dims


def read(run):
    c, tr = run.counters, run.trace
    t = (tr or {}).get("kernel_seconds", {}).get("flash_decode_paged")
    if not t or not c.get("decode_lengths"):
        return None
    d = Dims.of(run.cell.config)
    flops = byts = 0.0
    for n in c["decode_lengths"]:
        f, b = fd.step(d, n)
        flops += f
        byts += b
    least = max(flops / run.peaks["bf16_flop_per_s"],
                byts / run.peaks["hbm_byte_per_s"]) * d.layers
    return 100.0 * least / t
