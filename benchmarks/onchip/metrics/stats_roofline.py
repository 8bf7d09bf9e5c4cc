"""The stats kernel's share of its roofline, in %: the least time the
chip's HBM bandwidth allows for reading every probed tensor once at its
own dtype (flops.stats_bytes_per_step), over the kernel's device time
per step (the `stats_kernel_ms` reader). The kernel is bound by bytes,
not operations. None where the step runs no such kernel."""
from harness import metric_reader


def read(ctx):
    kernel_ms = metric_reader("stats_kernel_ms")(ctx)
    if kernel_ms is None or ctx["stats_bytes_per_step"] <= 0:
        return None
    least_s = ctx["stats_bytes_per_step"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (kernel_ms / 1e3)
