"""Reduce a profiler trace (``.xplane.pb``) to the numbers the metrics use:
per device, busy time (the union of op intervals), time per op and per
program, idle gaps, and the host spans that were open during each gap.

Only the span of the host annotation ``bench.window`` is read: device
events are clipped to it. Device planes are ``/device:TPU:<n>``; their ops
are on the line ``XLA Ops`` (asynchronous ones, such as a collective's
start, on ``Async XLA Ops``) and their programs on ``XLA Modules``. Host
spans are the benchmark's ``bench.*`` annotations. Busy time counts the
synchronous ops only.
"""
from __future__ import annotations

import glob
import heapq
from dataclasses import dataclass, field
from pathlib import Path

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10
NAME_CHARS = 160  # an op's name is its HLO text; the breakdown keeps its head


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def _subtract(a, b):
    """a minus b, both sorted and disjoint."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@dataclass
class Device:
    name: str
    ops: list[tuple[str, int, int]] = field(default_factory=list)  # (name, start, end)
    async_ops: list[tuple[str, int, int]] = field(default_factory=list)
    modules: list[tuple[str, int, int]] = field(default_factory=list)

    def busy(self) -> list[tuple[int, int]]:
        return _union([(s, e) for _, s, e in self.ops])


@dataclass
class Summary:
    window: tuple[int, int]  # ns, on the trace's clock
    devices: list[Device]
    spans: list[tuple[str, int, int]]  # host bench.* spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices."""
        return sum(_length(d.busy()) for d in self.devices) * 1e-9 / len(self.devices)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def op_seconds(self, match) -> float:
        """Device seconds of ops whose name satisfies ``match``, averaged
        over the devices."""
        tot = sum(e - s for d in self.devices for n, s, e in d.ops if match(n))
        return tot * 1e-9 / len(self.devices)

    def op_count(self, match) -> int:
        return sum(1 for d in self.devices for n, _, _ in d.ops + d.async_ops if match(n))

    def module_seconds(self, match) -> float:
        tot = sum(e - s for d in self.devices for n, s, e in d.modules if match(n))
        return tot * 1e-9 / len(self.devices)

    def gaps(self, device: int = 0) -> list[tuple[int, int]]:
        busy = self.devices[device].busy()
        return _subtract([self.window], busy)

    def span_at(self, t: int) -> str:
        """The innermost host span open at time ``t``."""
        best = None
        for n, s, e in self.spans:
            if s <= t < e and n != WINDOW_SPAN and (best is None or s >= best[1]):
                best = (n, s)
        return best[0] if best else "none"

    def breakdown(self) -> dict:
        per_op: dict[str, int] = {}
        for d in self.devices:
            for n, s, e in d.ops:
                per_op[n] = per_op.get(n, 0) + (e - s)
        ops = heapq.nlargest(TOP, per_op.items(), key=lambda kv: kv[1])
        longest = heapq.nlargest(TOP, self.gaps(0), key=lambda g: g[1] - g[0])
        return {
            "device_ops": [[n[:NAME_CHARS], t * 1e-9 / len(self.devices)] for n, t in ops],
            "idle_gaps": [[self.span_at((s + e) // 2), (e - s) * 1e-9] for s, e in longest],
        }


def _events(line, lo: int, hi: int):
    for ev in line.events:
        s = int(ev.start_ns)
        e = s + int(ev.duration_ns)
        if e > lo and s < hi:
            yield ev.name, max(s, lo), min(e, hi)


def find_xplane(path: Path) -> Path:
    path = Path(path)
    if path.is_file():
        return path
    found = sorted(glob.glob(str(path / "**" / "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return Path(found[-1])


def summarize(path, n_devices: int | None = None) -> Summary:
    """Read the trace at ``path`` (a file or a directory holding one)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(find_xplane(path)))
    return summarize_data(pd, n_devices)


def summarize_data(pd, n_devices: int | None = None) -> Summary:
    spans: list[tuple[str, int, int]] = []
    device_planes = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        s = int(ev.start_ns)
                        spans.append((ev.name, s, s + int(ev.duration_ns)))
        elif plane.name.startswith("/device:TPU:") and plane.name[len("/device:TPU:"):].isdigit():
            device_planes.append(plane)
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo, hi = windows[0]
    device_planes.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    if n_devices is not None:
        device_planes = device_planes[:n_devices]
    devices = []
    for plane in device_planes:
        dev = Device(plane.name)
        for line in plane.lines:
            if line.name == OPS_LINE:
                dev.ops.extend(_events(line, lo, hi))
            elif line.name == ASYNC_LINE:
                dev.async_ops.extend(_events(line, lo, hi))
            elif line.name == MODULES_LINE:
                dev.modules.extend(_events(line, lo, hi))
        devices.append(dev)
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    spans.sort(key=lambda x: x[1])
    return Summary(window=(lo, hi), devices=devices, spans=spans)


def describe(path, top: int = 25) -> None:
    """Print every plane and line of a trace, with each line's most
    time-consuming event names: the look by hand before a reader relies on
    a name."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(find_xplane(path)))
    for plane in pd.planes:
        print(f"PLANE {plane.name}")
        for line in plane.lines:
            tot: dict[str, list] = {}
            for ev in line.events:
                t = tot.setdefault(ev.name, [0, 0])
                t[0] += 1
                t[1] += int(ev.duration_ns)
            print(f"  LINE {line.name!r}: {sum(c for c, _ in tot.values())} events")
            for name, (c, ns) in heapq.nlargest(top, tot.items(), key=lambda kv: kv[1][1]):
                print(f"    {ns * 1e-6:12.3f} ms {c:7d}x {name[:160]}")


if __name__ == "__main__":
    import sys

    describe(sys.argv[1])
