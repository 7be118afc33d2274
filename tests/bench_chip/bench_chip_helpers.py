"""Drive a whole benchmark run on the CPU at a tiny size: everything but the
harness's look for a chip. The tiny configurations keep the architecture of
the configuration they stand for, and take its limits."""
from __future__ import annotations

import json
from pathlib import Path

from benchmarks.chip import run as bench

DATA = Path(__file__).resolve().parent / "data"
MANIFEST = json.loads((bench.ROOT / "BENCHMARK.json").read_text())
STANDS_FOR = {  # tiny configuration -> (the configuration it stands for, tiny traffic)
    "tiny-qwen3": ("qwen3-0.6b", "tiny-chat"),
}


def config_file(name: str) -> dict:
    return json.loads((bench.HERE / "configs" / f"{name}.json").read_text())


def tiny_cell(config_name: str, *, seed: int, seconds: float, chips: int = 1):
    real, traffic_name = STANDS_FOR[config_name]
    config = json.loads((DATA / f"{config_name}.json").read_text())
    config["limits"] = config_file(real)["limits"]
    traffic = json.loads((DATA / f"{traffic_name}.json").read_text())
    model = bench.model_numbers(config)
    cfg = bench.program_config(config, model)
    driver_name = traffic["driver"]
    driver = bench.load_module(bench.HERE / "drivers" / f"{driver_name}.py",
                               f"benchmarks.chip.drivers.{driver_name}")
    cell = bench.Cell(name=config_name, chips=chips, seed=seed, seconds=seconds, trace=False,
                      config=config, traffic=traffic, model=model, cfg=cfg,
                      device={"platform": "cpu", "kind": "cpu", "count": chips}, peaks={})
    return cell, driver


def run_tiny(config_name: str, *, seed: int, seconds: float, chips: int = 1, controls=()):
    cell, driver = tiny_cell(config_name, seed=seed, seconds=seconds, chips=chips)
    result, _ = bench.execute(cell, driver, MANIFEST, controls=controls)
    return result
