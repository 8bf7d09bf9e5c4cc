"""Host milliseconds a step in which the runtime's publish copies the
device map states to the host (the `publish.fetch` span), averaged over
the `train.step` spans that lie wholly inside the traced window. None
where the program opens no such span."""
import program_trace


def read(ctx):
    ns = [sum(e - s for name, s, e, _ in inner if name == "publish.fetch")
          for _, inner in program_trace.steps(ctx)
          if any(s[0] == "publish.fetch" for s in inner)]
    if not ns:
        return None
    return sum(ns) / len(ns) / 1e6
