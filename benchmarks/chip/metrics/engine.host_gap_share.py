"""Share of the engine's ticks in which chip 0 idled, on the device's clock:
idle time inside the ticks in the traced window over their length. The
host's clock is moved onto the device's by the offset under which each run
of the read-back program (``jnp.argmax``) lies inside a tick
(``program_spans.clock_offset_ns``). The ticks are the driver's
``bench.step`` spans, each around one ``ServingEngine.step()`` and nothing
else: the trace summary keeps the ``bench.*`` spans, and the engine's own
``engine.step`` starts and ends within microseconds of them. Idle time
while the engine has work: the host's dispatch, write-back, read-back and
Python between the calls."""
from benchmarks.chip import program_spans


def read(run):
    tr = run.trace
    ticks = [(s, e) for n, s, e in tr.spans if n == "bench.step"]
    offset = program_spans.clock_offset_ns(program_spans.read_backs(tr), ticks)
    share = program_spans.idle_share(tr, ticks, offset)
    return None if share is None else 100.0 * share
