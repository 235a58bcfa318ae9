"""The SSD kernels' share of their roofline in training (%): the least
time the required work of every call in the traced window could take on
this chip (``bench/costs/ssd.py``; forward and backward once per Mamba2
layer per member per fused call), over the device time of the
``ssd_fwd`` and ``ssd_bwd`` kernels (both runs of ``ssd_fwd`` where the
layer is recomputed)."""
from bench import program_trace
from bench.costs import ssd
from bench.reference_hybrid import Dims

KERNELS = ("ssd_fwd", "ssd_bwd")


def read(run):
    c, cell = run.counters, run.cell
    by_name = (program_trace.of(run) or {}).get("kernel_seconds_by_name", {})
    t = sum(by_name.get(k, 0.0) for k in KERNELS)
    if not t or not c.get("calls"):
        return None
    pf, pb = run.peaks["bf16_flop_per_s"], run.peaks["hbm_byte_per_s"]
    least = 0.0
    for m in cell.traffic["members"]:
        d = Dims.of(cell.configs[m["config"]])
        for f, b in (ssd.forward(d, c["batch"], c["seq"]),
                     ssd.backward(d, c["batch"], c["seq"])):
            least += max(f / pf, b / pb) * d.kinds.count("mamba")
    return 100.0 * least * c["calls"] / t
