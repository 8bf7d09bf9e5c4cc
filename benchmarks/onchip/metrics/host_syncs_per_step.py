"""Device-to-host reads a step made by the training loop and by the
runtime's map publish: the `d2h` counter of each `train.step` span,
averaged over the steps that lie wholly inside the traced window. None
where the program opens no `train.step` span."""
import program_trace


def read(ctx):
    counts = [step[3]["d2h"] for step, _ in program_trace.steps(ctx)
              if "d2h" in step[3]]
    if not counts:
        return None
    return sum(counts) / len(counts)
