"""Device milliseconds per step of the Pallas tensor-statistics kernel
(kernels/tensor_stats.py): the summed durations of its operations in the
traced steps, over the steps. None where the step runs no such kernel."""
import xplane

# the kernel's custom call is named after the jitted wrapper that holds its
# pallas_call (`tensor_stats_pallas.N`, `jvp_jit_tensor_stats_pallas__.N`
# under value_and_grad); the trace gives the op as its HLO text
WRAPPER = "tensor_stats_pallas"


def is_kernel(text: str) -> bool:
    return WRAPPER in xplane.op_name(text) and \
        (" = " not in text or "tpu_custom_call" in text)


def read(ctx):
    ns = sum(e - s for text, s, e, _ in ctx["ops"] if is_kernel(text))
    if ns <= 0:
        return None
    return ns / 1e6 / ctx["steps"]
