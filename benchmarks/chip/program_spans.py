"""The program's own spans on the device trace's clock.

The serving engine (``runtime/serve.py``) records each tick and its phases as
``jax.profiler.TraceAnnotation``s named ``engine.*``; all but the tick carry
the request id they concern as the stat ``rid``. This module reads them from
a profiler trace, estimates the device clock's offset from the host's, and
names each instant the device idles by the innermost program span open then.

The host's and the device's clocks in a trace disagree (the device's ran
1.1 to 2.4 ms behind in one-chip v5e traces), by more than the gaps
between a tick's decode calls last. The offset is estimated from the trace itself
(``clock_offset_ns``): each run of the read-back program (``jnp.argmax``
of the sampled logits) lies wholly inside the host span that dispatched it
and then waited for its result (``engine.sample``, or the tick around it).
The device's idle gaps alone do not fix it: the host's small ops (the
next token batch, the position) cut the gaps after a read-back into
pieces, and the read-backs' ends fit several placements.

    python3 benchmarks/chip/program_spans.py <trace file or directory>

reads a profile of the engine: one the benchmark recorded (its window is
then the ``bench.window`` span) or any other, such as one taken around
``ServingEngine.run_until_drained()`` with ``jax.profiler.trace(<dir>)``
(its window is then the ticks' extent). It prints one JSON object: the
window's seconds, each span's count and host seconds in it, and, where the
profile holds a TPU, the offset with the read-backs it rests on, chip 0's
idle seconds, those by innermost program span (``outside`` where none is
open; they add up to the idle seconds) and the ticks' idle share.
"""
from __future__ import annotations

import bisect
import heapq
import json
import sys
from pathlib import Path

if __name__ == "__main__":  # run as a script: import the harness as a package
    sys.path[0] = str(Path(__file__).resolve().parents[2])

from benchmarks.chip import trace  # noqa: E402

PREFIX = "engine."
TICK = "engine.step"
READ_BACK = "engine.sample"
READ_BACK_PROGRAM = "jit__argmax"  # the device's name for the sampled argmax
CHIP0 = "/device:TPU:0"
OUTSIDE = "outside"
SEARCH_NS = 5_000_000  # offsets searched: +-5 ms
MIN_ITEMS = 20  # fewer read-backs than this: no estimate (0)
EVERYWHERE = (float("-inf"), float("inf"))


def _clipped(events, window: tuple[int, int]):
    """``(event, start, end)`` of the ``events`` that overlap ``window``,
    their times clipped to it."""
    lo, hi = window
    for ev in events:
        s = int(ev.start_ns)
        e = s + int(ev.duration_ns)
        if e > lo and s < hi:
            yield ev, max(s, lo), min(e, hi)


def _host_events(pd, prefix: str):
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                yield from (ev for ev in line.events if ev.name.startswith(prefix))


def collect(pd, window: tuple[int, int] = EVERYWHERE):
    """Host events of ``pd`` (a ``jax.profiler.ProfileData``) whose names
    start with ``PREFIX``, as ``(name, start, end, rid or None)`` on the
    trace's clock, clipped to ``window``; sorted by start, an enclosing span
    before the spans it holds."""
    out = []
    for ev, s, e in _clipped(_host_events(pd, PREFIX), window):
        rid = dict(ev.stats).get("rid")
        out.append((ev.name, s, e, None if rid is None else int(rid)))
    out.sort(key=lambda x: (x[1], -x[2]))
    return out


def window_of(pd) -> tuple[int, int] | None:
    """The benchmark's ``bench.window`` span where ``pd`` has one, else
    the extent of the engine's ticks; None without either."""
    for _, s, e in _clipped(_host_events(pd, trace.WINDOW_SPAN), EVERYWHERE):
        return s, e
    ticks = [(s, e) for _, s, e in _clipped(_host_events(pd, TICK), EVERYWHERE)]
    return (min(s for s, _ in ticks), max(e for _, e in ticks)) if ticks else None


def chip0(pd, window: tuple[int, int]) -> trace.Summary | None:
    """Chip 0's ops and programs in ``window``, read as ``trace`` reads
    them, as a ``trace.Summary``; None where ``pd`` holds no TPU."""
    plane = next((p for p in pd.planes if p.name == CHIP0), None)
    if plane is None:
        return None
    dev = trace.Device(plane.name)
    kept = {trace.OPS_LINE: dev.ops, trace.MODULES_LINE: dev.modules}
    for line in plane.lines:
        if line.name in kept:
            kept[line.name].extend((ev.name, s, e) for ev, s, e in _clipped(line.events, window))
    return trace.Summary(window=window, devices=[dev], spans=[])


def read_backs(summary: trace.Summary):
    """Chip 0's runs of the engine's read-back program, ``jnp.argmax``
    under jit, as ``(start, end)`` on the device's clock."""
    return sorted((s, e) for n, s, e in summary.devices[0].modules
                  if n.startswith(READ_BACK_PROGRAM))


def clock_offset_ns(items, containers) -> int:
    """The device clock minus the host's, in ns. ``items`` are intervals
    on the device's clock, each of which ran wholly inside one of the host
    intervals ``containers`` (sorted, disjoint): a read-back program inside
    the host span that dispatched it and waited for its result. Of the
    shifts within ``+-SEARCH_NS``, those that fit the most items into
    containers form ranges; of the range nearest 0, the smallest shift.
    That edge is tight, the other loose: a read-back returns soon after its
    program ends, while the program starts well after its span opens. 0
    with fewer than ``MIN_ITEMS`` items."""
    if len(items) < MIN_ITEMS or not containers:
        return 0
    ends = [e for _, e in containers]
    edges = []
    for a, b in items:
        j = bisect.bisect_left(ends, b - SEARCH_NS)
        while j < len(containers) and containers[j][0] <= a + SEARCH_NS:
            lo = max(b - containers[j][1], -SEARCH_NS)
            hi = min(a - containers[j][0], SEARCH_NS)
            if lo <= hi:
                edges.append((lo, 0))  # opens before a close at the same shift
                edges.append((hi, 1))
            j += 1
    best, ranges, depth, opened = 0, [], 0, None
    for x, kind in sorted(edges):
        if kind == 0:
            depth += 1
            if depth > best:
                best, ranges = depth, []
            if depth == best:
                opened = x
        else:
            if depth == best and opened is not None:
                ranges.append((opened, x))
            opened = None
            depth -= 1
    if not ranges:
        return 0
    nearest = min(ranges, key=lambda r: 0 if r[0] <= 0 <= r[1] else min(abs(r[0]), abs(r[1])))
    return int(nearest[0])


def shift(spans, offset: int, window: tuple[int, int]):
    """``(name, start, end, ...)`` spans moved by ``offset`` and clipped to
    ``window``; those left empty are dropped."""
    lo, hi = window
    out = []
    for name, s, e, *rest in spans:
        s, e = max(s + offset, lo), min(e + offset, hi)
        if s < e:
            out.append((name, s, e, *rest))
    return out


def idle_share(summary: trace.Summary, ticks, offset: int) -> float | None:
    """Share of the host intervals ``ticks`` (sorted, disjoint), moved by
    ``offset`` onto the device's clock and clipped to the window, in which
    chip 0 idled; None where none is left."""
    moved = [(s, e) for _, s, e in shift([(None, s, e) for s, e in ticks],
                                         offset, summary.window)]
    length = sum(e - s for s, e in moved)
    if not length:
        return None
    gaps, idle, i, j = summary.gaps(0), 0, 0, 0
    while i < len(moved) and j < len(gaps):
        idle += max(0, min(moved[i][1], gaps[j][1]) - max(moved[i][0], gaps[j][0]))
        if moved[i][1] < gaps[j][1]:
            i += 1
        else:
            j += 1
    return idle / length


def _innermost(spans, window: tuple[int, int]):
    """``window`` cut into ``(start, end, name)`` pieces, each named by the
    innermost span of ``spans`` (sorted as ``collect`` sorts them) open in
    it: the latest started. ``OUTSIDE`` where none is."""
    lo, hi = window
    bounds = []
    for k, (_, s, e, *_) in enumerate(spans):
        s, e = max(s, lo), min(e, hi)
        if s < e:
            bounds.append((s, 1, k))
            bounds.append((e, 0, k))
    bounds.sort()  # at one instant, ends before starts; outer spans first
    out, stack, cur = [], [], lo
    for t, opens, k in bounds:
        if t > cur:
            out.append((cur, t, spans[stack[-1]][0] if stack else OUTSIDE))
            cur = t
        if opens:
            stack.append(k)
        else:
            stack.remove(k)
    if cur < hi:
        out.append((cur, hi, OUTSIDE))
    return out


def idle_by_span(spans, gaps, window: tuple[int, int]) -> dict[str, float]:
    """Idle seconds in ``window`` by the innermost span open at each idle
    instant (spans already on the device's clock). The values add up to the
    idle time of ``gaps`` in ``window``."""
    out: dict[str, float] = {}
    pieces = _innermost(spans, window)
    i = j = 0
    while i < len(pieces) and j < len(gaps):
        s, e, name = pieces[i]
        ov = min(e, gaps[j][1]) - max(s, gaps[j][0])
        if ov > 0:
            out[name] = out.get(name, 0.0) + ov * 1e-9
        if e < gaps[j][1]:
            i += 1
        else:
            j += 1
    return out


def reduce(spans, window: tuple[int, int], chip: trace.Summary | None = None) -> dict:
    """What the program's spans (clipped to ``window``) say: each span's
    count and host seconds; with ``chip`` (chip 0 over ``window``) also the
    clock offset (each read-back program inside its ``engine.sample``
    span) and the read-backs it rests on, the idle seconds, those by
    innermost span (the top ``trace.TOP``) and the ticks' idle share, with
    the offset applied."""
    per_name: dict[str, list] = {}
    for name, s, e, _ in spans:
        c = per_name.setdefault(name, [0, 0.0])
        c[0] += 1
        c[1] += (e - s) * 1e-9
    out = {"window_s": (window[1] - window[0]) * 1e-9, "spans": per_name}
    if chip is None:
        return out
    gaps = chip.gaps(0)
    items = read_backs(chip)
    offset = clock_offset_ns(items, [(s, e) for n, s, e, _ in spans if n == READ_BACK])
    by_span = idle_by_span(shift(spans, offset, window), gaps, window)
    out.update({
        "clock_offset_ns": offset,
        "read_backs": len(items),
        "idle_s": sum(e - s for s, e in gaps) * 1e-9,
        "idle_by_program_span": [[n, t] for n, t in
                                 heapq.nlargest(trace.TOP, by_span.items(), key=lambda kv: kv[1])],
        "tick_idle_share": idle_share(chip, [(s, e) for n, s, e, _ in spans if n == TICK], offset),
    })
    return out


def read(pd) -> dict:
    """``reduce`` over the window of ``pd`` (``window_of``)."""
    window = window_of(pd)
    if window is None:
        raise ValueError(f"the profile holds neither a {trace.WINDOW_SPAN!r} nor a {TICK!r} span")
    return reduce(collect(pd, window), window, chip0(pd, window))


def main(argv=None) -> int:
    from jax.profiler import ProfileData

    path = (argv or sys.argv[1:])[0]
    print(json.dumps(read(ProfileData.from_file(str(trace.find_xplane(path))))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
