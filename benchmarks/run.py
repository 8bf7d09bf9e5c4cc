"""CPU benchmark harness of the probe pipeline, the map ops and the
dry-run roofline.

    PYTHONPATH=src python -m benchmarks.run [--fast]
    PYTHONPATH=src python -m benchmarks.run --json BENCH_probe.json

Sections:
  maps     map-op throughput (ref vs Pallas-interpret)
  probe    probe-stage ns/event per exec mode (scan/vectorized/fused/
           interp — the live program-table lane) + live attach latency
  roofline aggregate of dry-run cells (results/*.json), if present

`--json PATH` runs ONLY the probe-pipeline section and writes the
machine-readable BENCH_probe.json (ns/event per mode + fused-vs-scan
speedup) so subsequent PRs can track the perf trajectory. `--fast` shrinks
the tape (smoke-test mode).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np


def section(title):
    print(f"\n## {title}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--json", metavar="PATH",
                    help="write probe-pipeline results as JSON (runs only "
                         "that section)")
    ap.add_argument("--fleet-counts", default="32",
                    help="comma-separated worker counts for the "
                         "hierarchical fleet-scale sweep (json mode)")
    args = ap.parse_args(argv)

    if args.json:
        # temp-file + atomic rename: fail fast on a bad path without
        # truncating a previous run's results if the benchmark dies
        tmp = args.json + ".tmp"
        with open(tmp, "w"):
            pass
        from benchmarks import probe_pipeline
        counts = tuple(int(c) for c in args.fleet_counts.split(",") if c)
        res = probe_pipeline.run(n_events=512 if args.fast else 4096,
                                 iters=3 if args.fast else 10,
                                 fleet_counts=counts)
        with open(tmp, "w") as f:
            json.dump(res, f, indent=1)
        os.replace(tmp, args.json)
        section(f"probe_pipeline ({res['n_programs']} programs, "
                f"{res['n_events']} events)")
        for mode, r in res["modes"].items():
            print(f"{mode},{r['ns_per_event']:.1f}ns/event")
        if "speedup_fused_vs_scan" in res:
            print(f"# fused vs scan: {res['speedup_fused_vs_scan']:.1f}x")
        if "interp_overhead_vs_scan" in res:
            print(f"# interp lane vs scan: "
                  f"{res['interp_overhead_vs_scan']:.1f}x overhead")
        if "attach_latency_ms" in res:
            print(f"# live attach latency: "
                  f"{res['attach_latency_ms']:.2f}ms (retrace avoided: "
                  f"~{res['modes']['fused']['compile_s']}s)")
        if "promotion" in res:
            pr = res["promotion"]
            print(f"# promotion: interp->fused in "
                  f"{pr['time_to_fused_ms'] / 1e3:.1f}s (background), "
                  f"cached swap {pr['cached_swap_ms']:.1f}ms, "
                  f"bit_identical={pr['bit_identical']}")
        if "fleet" in res:
            print(f"# fleet merge: {res['fleet']['events_per_s']:.0f} "
                  f"events/s across {res['fleet']['workers']} workers")
        if "fleet_recovery" in res:
            fr = res["fleet_recovery"]
            print(f"# fleet recovery: {fr['recovery_ms']:.1f}ms daemon "
                  f"restart (zero_loss={fr['zero_loss']})")
        if "fleet_scale" in res:
            fs = res["fleet_scale"]
            for c in fs["curve"]:
                print(f"# fleet scale: {c['workers']}w tree "
                      f"{c['tree_events_per_s']:.0f} events/s "
                      f"({c['tree_speedup_vs_flat3']:.1f}x vs flat-3, "
                      f"bit_identical={c['bit_identical']})")
        if "widening" in res:
            wf, wb = res["widening"]["fused"], res["widening"]["batched"]
            print(f"# widening: disjoint-update set fused at "
                  f"{wf['ns_per_event']:.0f}ns/event "
                  f"({wf['speedup']:.1f}x vs scan fallback), shared-hash "
                  f"slots batched at {wb['ns_per_event']:.0f}ns/event "
                  f"({wb['speedup']:.1f}x vs demoted row loop)")
        print(f"\nwrote {args.json}\nOK")
        return

    section("map_ops (us/batch of 256 events)")
    from repro.kernels import ops
    keys = jnp.asarray(np.random.default_rng(0).integers(0, 64, 256),
                       jnp.int64)
    deltas = jnp.ones((256,), jnp.int64)
    valid = jnp.ones((256,), bool)
    kt = jnp.zeros((64,), jnp.int64)
    for impl in ("ref",) + (() if args.fast else ("pallas_interpret",)):
        f = jax.jit(lambda a, b, c, d_, e, f_: ops.hash_fetch_add_batch(
            a, b, c, d_, e, f_, impl=impl))
        out = f(kt, kt, kt, keys, deltas, valid)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(20):
            out = f(kt, kt, kt, keys, deltas, valid)
        jax.block_until_ready(out)
        print(f"hash_fetch_add_batch[{impl}],"
              f"{(time.perf_counter() - t0) / 20 * 1e6:.1f}")

    section("probe_pipeline (ns/event per mode)")
    from benchmarks import probe_pipeline
    res = probe_pipeline.run(n_events=512 if args.fast else 4096,
                             iters=3 if args.fast else 10)
    for mode, r in res["modes"].items():
        print(f"{mode},{r['ns_per_event']:.1f}")
    if "speedup_fused_vs_scan" in res:
        print(f"# fused vs scan: {res['speedup_fused_vs_scan']:.1f}x")

    section("roofline (from dry-run results/)")
    try:
        from benchmarks import roofline_report
        roofline_report.main("results")
    except Exception as e:
        print(f"(no dry-run results yet: {e})")

    print("\nOK")


if __name__ == "__main__":
    main()
