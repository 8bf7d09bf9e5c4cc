"""Device milliseconds a step of the probe stage (core/runtime.py
`probe_stage`): the own time of every operation traced under a
`jax.named_scope("probe.stage.<lane>")` (vector, combined_scan, scan,
vectorized, table), over the traced steps. None where no operation
carries such a scope."""
import program_trace


def read(ctx):
    ops = program_trace.scoped(ctx, "probe.stage.")
    if not ops:
        return None
    return sum(ns for ns, _ in ops) / 1e6 / ctx["steps"]
