"""The engine's jitted decode step against its roofline: the least time the
chip needs for what the traced ticks' decode calls must do (each call: every
weight once, the live K/V rows of the one sequence it advances, the row it
writes; ``work.decode_call``), over the device time of the decode program in
the trace. Reading the whole cache, copying it and decoding the other lanes
count as waste. Memory-bound at these sizes."""
from benchmarks.chip import work
from benchmarks.chip.metrics import _common


def read(run):
    t_dev = run.trace.module_seconds(_common.is_decode_module)
    if t_dev <= 0:
        return None
    need = 0.0
    for tick in _common.traced_ticks(run):
        need += work.roofline_seconds(tick[2], tick[3], run.cell.peaks)[0]
    return 100.0 * need / t_dev
