"""Chip benchmark: run one cell of ``BENCHMARK.json`` on this machine's chips.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The cell names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<traffic>.json``);
the mix names its generator (``traffic/<generator>.py``) and the entry point
the window drives (``drivers/<driver>.py``). With ``--trace 0`` the result
carries the cell's end-to-end metrics; with ``--trace 1`` a profiler trace of
the window is reduced by ``trace.py`` and each per-layer metric that lists
the cell is read by ``metrics/<metric>.py``.

Without a TPU, or with fewer chips than the cell asks for, it exits 3 and
prints no result. The persistent compilation cache is
``$JAX_COMPILATION_CACHE_DIR`` when that is set, else ``.jax_cache`` in the
checkout. The last line of standard output is the result (JSON); the line
before it counts the requests and how late they were submitted; the last
lines of standard error give each number compared beside its limit.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
TRACE_DIR = ROOT / ".bench_trace"
# run as a script: import the harness as a package, not its files as
# top-level modules (``trace`` would shadow the standard library's)
if sys.path and Path(sys.path[0]).resolve() == HERE:
    sys.path.pop(0)
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import jax  # noqa: E402

from benchmarks.chip import trace as trace_mod  # noqa: E402
from benchmarks.chip import work  # noqa: E402

EXIT_NO_DEVICE = 3

# published config.json keys -> the benchmark's model numbers
PUBLISHED_KEYS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "tie_word_embeddings": "tie_embeddings", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "norm_epsilon": "norm_eps",
}
# the benchmark's model numbers -> the program's ArchConfig fields
PROGRAM_FIELDS = {
    "num_layers": "num_layers", "d_model": "d_model", "num_heads": "num_heads",
    "num_kv_heads": "num_kv_heads", "head_dim": "resolved_head_dim", "d_ff": "d_ff",
    "vocab_size": "vocab_size", "tie_embeddings": "tie_embeddings",
    "rope_theta": "rope_theta", "norm_eps": "norm_eps", "mlp": "mlp", "qk_norm": "qk_norm",
}


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def load_module(path: Path, name: str):
    """A harness file found by name (file names may hold dots)."""
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_numbers(config: dict) -> dict:
    """The model as run: the published numbers, renamed, and the
    architecture's own choices (MLP kind, qk-norm)."""
    m = dict(config["architecture"])
    for key, val in config["published"].items():
        if key in PUBLISHED_KEYS:
            m[PUBLISHED_KEYS[key]] = val
    return m


def program_config(config: dict, model: dict):
    """The program's ArchConfig for this file; every number must agree."""
    from repro.configs import get_config

    prog = config["program"]
    cfg = dataclasses.replace(get_config(prog["arch"]), **prog.get("overrides", {}))
    for key, field in PROGRAM_FIELDS.items():
        if getattr(cfg, field) != model[key]:
            raise ValueError(f"{prog['arch']}: the program has {field}={getattr(cfg, field)!r},"
                             f" the configuration file {key}={model[key]!r}")
    return cfg


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    seed: int
    seconds: float
    trace: bool
    config: dict
    traffic: dict
    model: dict
    cfg: object
    device: dict
    peaks: dict

    def limit(self, key: str) -> float:
        return self.config["limits"][key]


class Clock:
    """The window's host clock, its spans, and the profiler around it.

    Spans are ``jax.profiler.TraceAnnotation``s (in the trace when one is
    being recorded). A traced run records ``trace_seconds`` from the middle
    of the window (all of it by default), starting and stopping only
    between ticks."""

    def __init__(self, seconds: float, trace_seconds: float | None):
        self.seconds = seconds
        self.trace_seconds = trace_seconds
        self.t0 = self.t_close = None
        self.trace_t0 = self.trace_t1 = None
        self._ann = None
        self.compiles = 0
        self._in_window = False
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if self._in_window and event in (
                "/jax/core/compile/backend_compile_duration",
                "/jax/compilation_cache/cache_retrieval_time_sec"):
            self.compiles += 1

    def span(self, name: str):
        return jax.profiler.TraceAnnotation(name)

    def start_window(self, t0: float | None = None) -> float:
        """Open the window now, or at ``t0`` (a moment on this clock that
        has just passed)."""
        self._in_window = True
        self.t0 = time.perf_counter() if t0 is None else t0
        return self.t0

    def end_window(self, t_close: float) -> None:
        self._in_window = False
        self.t_close = t_close

    def maybe_trace(self, now: float) -> None:
        if self.trace_seconds is None:
            return
        if self.trace_t0 is None:
            lead = max(0.0, (self.seconds - self.trace_seconds) / 2)
            if now >= self.t0 + lead:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                shutil.rmtree(TRACE_DIR, ignore_errors=True)
                jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
                self._ann = jax.profiler.TraceAnnotation("bench.window")
                self._ann.__enter__()
                self.trace_t0 = time.perf_counter()
        elif self.trace_t1 is None and now >= self.trace_t0 + self.trace_seconds:
            self.stop_trace()

    def stop_trace(self) -> None:
        if self.trace_t0 is not None and self.trace_t1 is None:
            self.trace_t1 = time.perf_counter()
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()


@dataclasses.dataclass
class Run:
    """What a per-layer metric reader gets: the cell, the driver's host
    records, the trace summary, and the traced window's host-clock bounds."""
    cell: Cell
    rec: dict
    trace: object
    clock: Clock


def device_or_exit(chips: int) -> dict:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"run.py: needs a TPU, JAX found platform {devs[0].platform!r}", file=sys.stderr)
        sys.exit(EXIT_NO_DEVICE)
    if len(devs) < chips:
        print(f"run.py: the cell needs {chips} chips, JAX found {len(devs)}", file=sys.stderr)
        sys.exit(EXIT_NO_DEVICE)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def memory_peak(chips: int) -> int | None:
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def per_layer_for(manifest: dict, cell_name: str) -> list[dict]:
    """Per-layer metrics that this cell reports: those listing it, and those
    without a list whose end-to-end metric the cell reports."""
    e2e = {m["name"] for m in e2e_for(manifest, cell_name)}
    out = []
    for m in manifest["per_layer"]:
        if cell_name in m.get("workloads", []) or ("workloads" not in m and m["moves"] in e2e):
            out.append(m)
    return out


def e2e_for(manifest: dict, cell_name: str) -> list[dict]:
    return [m for m in manifest["end_to_end"] if "workloads" not in m or cell_name in m["workloads"]]


def configure_cache() -> None:
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def build_cell(manifest: dict, args, device: dict | None) -> tuple[Cell, object]:
    entry = next((w for w in manifest["workloads"] if w["name"] == args.workload), None)
    if entry is None:
        raise SystemExit(f"run.py: no workload {args.workload!r} in BENCHMARK.json")
    config = load_json(HERE / "configs" / f"{entry['config']}.json")
    traffic = load_json(HERE / "traffic" / f"{entry['traffic']}.json")
    model = model_numbers(config)
    cfg = program_config(config, model)
    driver = load_module(HERE / "drivers" / f"{traffic['driver']}.py",
                         f"benchmarks.chip.drivers.{traffic['driver']}")
    cell = Cell(name=entry["name"], chips=entry["chips"], seed=args.seed,
                seconds=float(args.seconds), trace=bool(args.trace), config=config,
                traffic=traffic, model=model, cfg=cfg, device=device or {},
                peaks=work.peaks(device["kind"]) if device else {})
    return cell, driver


def execute(cell: Cell, driver, manifest: dict, *, controls=()) -> tuple[dict, list[str]]:
    """Set up, run the window, read memory, check; returns the result object
    and the lines of numbers compared. ``controls`` adds the control's
    readings to the summary."""
    clock = Clock(cell.seconds, cell.traffic.get("trace_seconds", cell.seconds)
                  if cell.trace else None)
    state = driver.setup(cell)
    rec = driver.window(cell, state, clock)
    setup_s = clock.t0 - T_PROCESS
    mem = memory_peak(cell.chips)
    driver.release(state)
    checks = driver.check(cell, state, rec, controls)
    compared = {k: v for k, v in checks.items() if isinstance(v, dict)}
    correct = all(v["value"] <= v["limit"] for v in compared.values())
    summary = driver.summary(rec)
    summary["compiles_in_window"] = clock.compiles
    summary.update({k: v for k, v in checks.items() if not isinstance(v, dict)})

    units = {m["name"]: m["unit"] for m in manifest["end_to_end"] + manifest["per_layer"]}
    device = dict(cell.device, memory_peak_bytes=mem)
    result = {"correct": correct, "attempted": summary["due"],
              "failed": int(checks.get("malformed_requests", {}).get("value", 0))}
    metrics = {}
    if cell.trace:
        tr = trace_mod.summarize(TRACE_DIR, n_devices=cell.chips)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        run = Run(cell=cell, rec=rec, trace=tr, clock=clock)
        for m in per_layer_for(manifest, cell.name):
            reader = load_module(HERE / "metrics" / f"{m['name']}.py",
                                 f"benchmarks.chip.metrics.{m['name']}")
            val = reader.read(run)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": units[m["name"]]}
        device.update(busy_s=tr.busy_s(), window_s=tr.window_s)
        result["breakdown"] = tr.breakdown()
    else:
        e2e = driver.end_to_end(cell, rec)
        e2e["setup_s"] = setup_s
        for m in e2e_for(manifest, cell.name):
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    result.update(metrics=metrics, device=device, summary=summary)
    result["checks"] = compared
    lines = [f"check {k}: {v['value']!r} (limit {v['limit']!r})" for k, v in compared.items()]
    return result, lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest = load_json(ROOT / "BENCHMARK.json")
    entry = next((w for w in manifest["workloads"] if w["name"] == args.workload), None)
    if entry is None:
        print(f"run.py: no workload {args.workload!r} in BENCHMARK.json", file=sys.stderr)
        return 2
    configure_cache()
    device = device_or_exit(entry["chips"])
    cell, driver = build_cell(manifest, args, device)
    result, lines = execute(cell, driver, manifest)
    summary = result.pop("summary")
    checks = result.pop("checks")
    print(json.dumps({"requests": summary}), flush=True)
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    with contextlib.suppress(BrokenPipeError):
        sys.exit(main())
