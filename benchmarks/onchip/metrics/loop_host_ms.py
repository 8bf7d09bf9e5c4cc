"""Host milliseconds a step that the training loop itself spends while
the device waits: each `train.step` span less its `train.wait` (blocked
on the step's results) and its `train.on_step` (the caller's hook),
averaged over the steps that lie wholly inside the traced window. None
where the program opens no `train.step` span."""
import program_trace


def read(ctx):
    steps = program_trace.steps(ctx)
    if not steps:
        return None
    ns = [step[2] - step[1] - sum(e - s for name, s, e, _ in inner
                                  if name in ("train.wait", "train.on_step"))
          for step, inner in steps]
    return sum(ns) / len(ns) / 1e6
