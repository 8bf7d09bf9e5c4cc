"""Mean host milliseconds per window step of the runtime's map publish
(`runtime.publish` -> core/shm.py), from the benchmark's span around the
runtime instance's `publish` call."""


def read(ctx):
    spans = [e - s for name, s, e in ctx["spans"] if name == "bench.publish"]
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
