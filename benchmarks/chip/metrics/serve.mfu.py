"""The serving step's share of the chip's bf16 peak: FLOPs needed for every
prompt and output token the traced ticks processed (``work.decode_call``),
over the device's busy seconds in the traced window times the peak."""
from benchmarks.chip.metrics import _common


def read(run):
    busy = run.trace.busy_s()
    flops = sum(t[2] for t in _common.traced_ticks(run))
    if busy <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (busy * run.cell.peaks["bf16_flops_per_s"])
