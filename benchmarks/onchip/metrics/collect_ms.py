"""Device milliseconds a step of the probe collection (core/events.py):
the own time of every operation traced under `jax.named_scope(
"probe.collect")` (casts and padding for the stats, event rows, their
stacking), less the stats kernel's custom calls that `stats_kernel_ms`
reads, over the traced steps. None where no operation carries the
scope."""
import program_trace
from harness import metric_reader

# the kernel rule of metrics/stats_kernel_ms.py, which the harness loads
# by path
is_kernel = metric_reader("stats_kernel_ms").__globals__["is_kernel"]


def read(ctx):
    ops = program_trace.scoped(ctx, "probe.collect")
    if not ops:
        return None
    return sum(ns for ns, text in ops if not is_kernel(text)) / 1e6 / \
        ctx["steps"]
