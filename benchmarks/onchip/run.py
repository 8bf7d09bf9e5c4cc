"""On-chip benchmark: one run of one cell of BENCHMARK.json.

    python3 benchmarks/onchip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for; it exits with 2 and prints no result when JAX finds no TPU
or too few chips. The last line of standard output is one JSON object:
`correct`, `attempted`, `failed`, `metrics` (the cell's end-to-end
metrics with `--trace 0`, its per-layer metrics with `--trace 1`),
`device`, with `--trace 1` also `breakdown`, and last `checks`: each
number compared for `correct` with its limit. The same numbers end
standard error.

JAX's persistent compilation cache is `<checkout>/.jax_cache`.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]


def _json_value(v):
    """A number for the JSON line; a non-finite one as its name."""
    v = float(v)
    return v if math.isfinite(v) else str(v)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(BENCH_DIR), str(ROOT / "src")]
    import harness
    bench = harness.benchmark()
    cell = harness.workload(bench, args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"run.py: the cell needs {cell['chips']} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro.jaxenv import use_compile_cache
    use_compile_cache()

    cfg = harness.config(cell["config"])
    import traffic
    mix = traffic.load(cell["traffic"])
    per_layer = tuple(m for m in bench["per_layer"]
                      if args.workload in m.get("workloads", [args.workload]))
    out = harness.run_cell(cfg, mix, harness.limits(args.workload),
                           cell=args.workload, seed=args.seed,
                           seconds=args.seconds, trace=bool(args.trace),
                           t_start=T_START, per_layer=per_layer)

    units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
    reported = [m["name"] for m in bench["end_to_end"]
                if args.workload in m.get("workloads", [args.workload])]
    if args.trace:
        metrics = out["per_layer"]
    else:
        metrics = {k: {"value": out["end_to_end"][k], "unit": units[k]}
                   for k in reported}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["chips"],
              "memory_peak_bytes": out["memory_peak_bytes"]}
    if args.trace:
        device.update(busy_s=out["busy_s"], window_s=out["window_s"])
    result = {"correct": bool(out["correct"]), "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = out["breakdown"]
    result["host"] = out["host"]
    result["checks"] = {k: {"value": _json_value(c["value"]),
                            "limit": c["limit"]}
                        for k, c in out["checks"].items()}
    print(f"window: {out['attempted']} steps, {out['window_compiles']} "
          f"compiles inside it; device bytes in use once the program's "
          f"state was freed: {out['bytes_in_use_after_window']}",
          file=sys.stderr)
    print(f"host: {json.dumps(out['host'])}", file=sys.stderr)
    for k, c in out["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {k}: {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
