"""What the metric readers share: how the device names the programs they
look for, and which host records fall in the traced window."""


def is_decode_module(name: str) -> bool:
    """The engine's jitted decode step (``jax.jit`` of a lambda)."""
    return name.startswith("jit__lambda")


def traced_ticks(run):
    lo, hi = run.clock.trace_t0, run.clock.trace_t1
    return [t for t in run.rec.get("ticks", []) if t[0] >= lo and t[1] <= hi]

