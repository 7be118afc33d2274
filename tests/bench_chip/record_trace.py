"""Record the small profiler trace that the trace-reduction tests read.

    python tests/bench_chip/record_trace.py OUT_DIR

Runs on the first device JAX finds: a jitted matmul step and the Pallas
flash kernel (on a TPU), each inside the host spans the benchmark uses.
Writes ``OUT_DIR/small.xplane.pb`` and prints every plane, line and the
distinct event names, so that a reader can see how the device names ops.
"""
from __future__ import annotations

import glob
import shutil
import sys
from pathlib import Path

import jax
import jax.numpy as jnp


def main() -> None:
    out = Path(sys.argv[1])
    out.mkdir(parents=True, exist_ok=True)
    repo = Path(__file__).resolve().parents[2]
    sys.path.insert(0, str(repo / "src"))
    devs = jax.devices()
    print("devices:", devs[0].platform, devs[0].device_kind, len(devs), flush=True)

    step = jax.jit(lambda w, x: jnp.tanh(x @ w) @ w.T)
    w = jnp.ones((1024, 1024), jnp.bfloat16) * 0.01
    x = jnp.ones((512, 1024), jnp.bfloat16)
    calls = [("bench.step", lambda: step(w, x))]
    if devs[0].platform == "tpu":
        from repro.kernels.flash_attention.kernel import flash_attention_tpu
        q = jnp.ones((1, 512, 16, 128), jnp.bfloat16)
        kv = jnp.ones((1, 512, 8, 128), jnp.bfloat16)
        flash = jax.jit(flash_attention_tpu)
        calls.append(("bench.flash", lambda: flash(q, kv, kv)))
    for _, fn in calls:  # compile outside the trace
        jax.block_until_ready(fn())

    tmp = out / "_trace"
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            for name, fn in calls:
                with jax.profiler.TraceAnnotation(name):
                    jax.block_until_ready(fn())
            with jax.profiler.TraceAnnotation("bench.wait"):
                jnp.zeros(()).block_until_ready()
    jax.profiler.stop_trace()
    src = sorted(glob.glob(str(tmp / "**" / "*.xplane.pb"), recursive=True))[-1]
    shutil.copy(src, out / "small.xplane.pb")
    shutil.rmtree(tmp)

    pd = jax.profiler.ProfileData.from_file(str(out / "small.xplane.pb"))
    for plane in pd.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            names = sorted({e.name for e in evs})
            print(f"  LINE {line.name!r} events={len(evs)} names={names[:40]}")
            for e in evs[:3]:
                print(f"    {e.name} start={e.start_ns} dur={e.duration_ns} "
                      f"stats={[(k, v) for k, v in e.stats][:8]}")


if __name__ == "__main__":
    main()
