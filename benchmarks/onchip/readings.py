"""Readings that the limits of `correct` are set from, on the chip.

    python3 benchmarks/onchip/readings.py <cell> --seeds 12 --out <file>

In one process, at the cell's own size: sound runs of the program on
`--seeds` seeds, then the control (the program's bfloat16-parameter path)
and the faults of `faults.py` on three seeds each. Every run is a full
set-up and the three steps the reference follows, with a window of no
length. Each run appends one JSON line: what ran, the seed, and every
number compared (limits are not applied). The benchmark's own runs never
run this.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]
FAULT_RUNS = ("half_batch", "altered_update", "half_tensor", "bf16_accum",
              "planted_nan", "altered_stats")


class _NoLimits(dict):
    def __missing__(self, key):
        return math.inf


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("cell")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=4000000001)
    ap.add_argument("--out", required=True)
    ap.add_argument("--kinds", default="sound,control," + ",".join(FAULT_RUNS))
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(BENCH_DIR), str(ROOT / "src")]
    import jax
    if jax.devices()[0].platform != "tpu":
        print("readings.py: no TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import faults
    import harness
    import traffic
    cell = harness.workload(harness.benchmark(), args.cell)
    cfg = harness.config(cell["config"])
    mix = traffic.load(cell["traffic"])
    for kind in args.kinds.split(","):
        n = args.seeds if kind == "sound" else 3
        for i in range(n):
            seed = args.first_seed + 7919 * i
            ctx = faults.FAULTS[kind]() if kind in faults.FAULTS \
                else contextlib.nullcontext()
            t = time.perf_counter()
            with ctx:
                out = harness.run_cell(cfg, mix, _NoLimits(), cell=args.cell,
                                       seed=seed, seconds=0, trace=False,
                                       t_start=t,
                                       control=(kind == "control"))
            line = {"cell": args.cell, "kind": kind, "seed": seed,
                    "seconds": time.perf_counter() - t,
                    "peak_hbm_gb": out["end_to_end"]["peak_hbm_gb"],
                    **{k: c["value"] for k, c in out["checks"].items()}}
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
