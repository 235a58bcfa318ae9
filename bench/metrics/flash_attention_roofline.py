"""The flash-attention kernels' share of their roofline in training (%):
the least time the required work of every call in the traced window
could take on this chip (``bench/costs/flash_attention.py``; forward and
backward once per layer per member per fused call), over the kernels'
time in the device trace."""
from bench.costs import flash_attention as fa
from bench.reference import Dims


def read(run):
    c, cell, tr = run.counters, run.cell, run.trace
    t = (tr or {}).get("kernel_seconds", {}).get("flash_attention")
    if not t or not c.get("calls"):
        return None
    pf, pb = run.peaks["bf16_flop_per_s"], run.peaks["hbm_byte_per_s"]
    least = 0.0
    for m in cell.traffic["members"]:
        d = Dims.of(cell.configs[m["config"]])
        for f, b in (fa.forward(d, c["batch"], c["seq"]),
                     fa.backward(d, c["batch"], c["seq"])):
            least += max(f / pf, b / pb) * d.layers
    return 100.0 * least * c["calls"] / t
