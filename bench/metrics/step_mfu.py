"""The whole step's share of the chip's peak bf16 rate (%): the matrix
products the work requires (``bench/costs/dense.py``), over the time of
the calls that did it.

Training: every member's forward and backward per fused call, over the
summed ``step_group`` walltimes.  Serving: the prefill rows computed (the
extend of a hit counts only its suffix) and the decode step of every
token received in the window, over the summed ``step_segment`` time."""
from bench.costs import dense
from bench.reference import Dims


def read(run):
    c, cell = run.counters, run.cell
    peak = run.peaks["bf16_flop_per_s"]
    if "step_group_s" in c:
        if not c["calls"]:
            return None
        members = cell.traffic["members"]
        per_call = sum(dense.train_step_flops(
            Dims.of(cell.configs[m["config"]]), c["batch"], c["seq"])
            for m in members)
        return 100.0 * per_call * c["calls"] / sum(c["step_group_s"]) / peak
    segs = c.get("segment_s")
    if not segs:
        return None
    d = Dims.of(cell.config)
    flops = sum(dense.prefill_flops(d, s, e) for s, e in c["prefills"])
    flops += sum(dense.decode_flops(d, n) for n in c["decode_lengths"])
    return 100.0 * flops / sum(segs) / peak
