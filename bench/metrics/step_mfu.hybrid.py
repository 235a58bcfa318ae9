"""The whole training step's share of the chip's peak bf16 rate (%) for
the hybrid of Mamba2 and attention layers: the matrix products the work
requires (``bench/costs/hybrid.py``: Mamba2 projections, SSD products,
MLPs, the attention's lower triangle, the head), forward and backward of
every member per fused call, over the summed ``step_group`` walltimes."""
from bench.costs import hybrid
from bench.reference_hybrid import Dims


def read(run):
    c, cell = run.counters, run.cell
    if not c.get("calls"):
        return None
    per_call = sum(hybrid.train_step_flops(
        Dims.of(cell.configs[m["config"]]), c["batch"], c["seq"])
        for m in cell.traffic["members"])
    return 100.0 * per_call * c["calls"] / sum(c["step_group_s"]) \
        / run.peaks["bf16_flop_per_s"]
