import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes, extract memory/cost/collective analysis, emit JSON for
EXPERIMENTS.md §Dry-run and §Roofline.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b \
        --shape train_4k [--multi-pod] [--probes] [--out results/]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

The XLA_FLAGS line above MUST precede every jax import (jax locks the
device count at first init) — hence the unusual module layout.
"""
import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp                      # noqa: E402

from repro.configs import SHAPES, registry, shape_applicable   # noqa: E402
from repro.dist import sharding as SH       # noqa: E402
from repro.launch import analysis, presets, specs as SP        # noqa: E402
from repro.launch.mesh import make_production_mesh             # noqa: E402

SDS = jax.ShapeDtypeStruct


def _state_shardings(state_shape, mesh):
    def one(path, leaf):
        keys = [str(getattr(p, "key", getattr(p, "idx", ""))) for p in path]
        return jax.sharding.NamedSharding(
            mesh, SH.spec_for(keys, leaf.shape, mesh))
    flat, treedef = jax.tree_util.tree_flatten_with_path(state_shape)
    return jax.tree_util.tree_unflatten(treedef,
                                        [one(p, l) for p, l in flat])


def _maybe_probe_runtime(cfg):
    """Representative bpftime instrumentation: per-layer activation stats
    into a hash map + rms histogram + router load for MoE."""
    from repro.core import maps as M
    from repro.core.runtime import BpftimeRuntime
    rt = BpftimeRuntime()
    rt.exec_mode = "scan"
    pid = rt.load_asm("layer_counts", """
        mov r9, r1                  ; save ctx across calls
        ldxdw r6, [r1+ctx:layer]
        stxdw [r10-8], r6
        lddw r1, map:layer_counts
        mov r2, r10
        add r2, -8
        mov r3, 1
        call map_fetch_add
        ldxdw r2, [r9+ctx:rms]
        lddw r1, map:rms_hist
        call hist_add
        mov r0, 0
        exit
    """, [M.MapSpec("layer_counts", M.MapKind.ARRAY, max_entries=128),
          M.MapSpec("rms_hist", M.MapKind.LOG2HIST)], "uprobe")
    rt.attach(pid, "uprobe:block")
    rt.attach(pid, "uretprobe:block")
    return rt


def build_cell(arch: str, shape_name: str, *, multi_pod: bool,
               probes: bool = False, probe_mode: str = "scan",
               donate: bool = True):
    """Returns (jitted, args, mesh, meta) ready to lower."""
    cfg = registry.get(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return None, None, None, {"skip": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    tcfg = presets.train_config(arch)
    rt = _maybe_probe_runtime(cfg) if probes else None

    if shape.mode == "train":
        from repro.train.train_step import (abstract_train_state,
                                            make_train_step)
        state_shape = abstract_train_state(cfg, tcfg, rt)
        state_sh = _state_shardings(state_shape, mesh)
        batch = SP.train_batch_specs(cfg, shape, tcfg)
        batch_sh = SP.batch_shardings(batch, mesh, cfg, shape, tcfg)
        step = make_train_step(cfg, tcfg, rt, probe_mode=probe_mode)
        jitted = jax.jit(step, in_shardings=(state_sh, batch_sh),
                         donate_argnums=(0,) if donate else ())
        args = (state_shape, batch)
    elif shape.mode == "prefill":
        from repro.serve.steps import make_prefill_step
        params = SP.abstract_params(cfg, tcfg.param_dtype)
        params_sh = _state_shardings(params, mesh)
        batch = SP.prefill_batch_specs(cfg, shape)
        batch_sh = SP.batch_shardings(
            batch, mesh, cfg, shape, presets.train_config(arch,
                                                          microbatch=0))
        dspec = SP.decode_specs(cfg, shape, tcfg.param_dtype)
        cache_sh = SP.cache_shardings(dspec["cache"], mesh, cfg, shape)
        maps = (jax.eval_shape(rt.init_device_maps) if rt else {})
        maps_sh = jax.tree.map(lambda _: SH.replicated(mesh), maps)
        step = make_prefill_step(cfg, rt)
        jitted = jax.jit(step, in_shardings=(params_sh, batch_sh, cache_sh,
                                             maps_sh),
                         donate_argnums=(2,) if donate else ())
        args = (params, batch, dspec["cache"], maps)
    else:  # decode
        from repro.serve.steps import make_decode_step
        params = SP.abstract_params(cfg, tcfg.param_dtype)
        params_sh = _state_shardings(params, mesh)
        dspec = SP.decode_specs(cfg, shape, tcfg.param_dtype)
        cache_sh = SP.cache_shardings(dspec["cache"], mesh, cfg, shape)
        tok_sh = SP.batch_shardings(
            {"tokens": dspec["tokens"]}, mesh, cfg, shape,
            presets.train_config(arch, microbatch=0))["tokens"]
        maps = (jax.eval_shape(rt.init_device_maps) if rt else {})
        maps_sh = jax.tree.map(lambda _: SH.replicated(mesh), maps)
        step = make_decode_step(cfg, rt, probe_mode=probe_mode)
        jitted = jax.jit(
            step,
            in_shardings=(params_sh, tok_sh, cache_sh, maps_sh,
                          SH.replicated(mesh)),
            donate_argnums=(2,) if donate else ())
        args = (params, dspec["tokens"], dspec["cache"], maps,
                SDS((), jnp.int32))

    meta = {"arch": arch, "shape": shape_name, "mode": shape.mode,
            "mesh": list(mesh.devices.shape), "multi_pod": multi_pod,
            "probes": probes}
    return jitted, args, mesh, meta


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             probes: bool = False, probe_mode: str = "scan",
             verbose: bool = True) -> dict:
    t0 = time.time()
    jitted, args, mesh, meta = build_cell(
        arch, shape_name, multi_pod=multi_pod, probes=probes,
        probe_mode=probe_mode)
    if jitted is None:
        return meta
    cfg = registry.get(arch)
    shape = SHAPES[shape_name]
    with SH.use_mesh(mesh):
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    out = dict(meta)
    out["lower_s"] = round(t_lower, 1)
    out["compile_s"] = round(t_compile, 1)

    try:
        ma = compiled.memory_analysis()
        out["memory_analysis"] = {
            k: int(getattr(ma, k)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(ma, k)}
    except Exception as e:  # CPU backend may not support it
        out["memory_analysis"] = {"error": str(e)}

    out["analytic_state_bytes_global"] = _analytic_bytes(args, mesh)

    cost = compiled.cost_analysis() or {}
    out["cost_xla_once"] = {          # XLA's own numbers (loop bodies x1)
        k: float(v) for k, v in cost.items()
        if isinstance(v, (int, float)) and
        k in ("flops", "bytes accessed", "optimal_seconds")}

    try:
        text = compiled.as_text()
    except Exception:
        text = lowered.as_text()
    from repro.launch import hlo_cost
    hc = hlo_cost.analyze(text)
    out["collectives"] = {
        "counts": {k: int(v) for k, v in hc.collective_counts.items()},
        "bytes_by_type": {k: float(v)
                          for k, v in hc.collective_bytes.items()},
        "wire_bytes_per_dev": hc.coll_wire,
        "flash_interior_bytes": hc.coll_bytes_flash_interior,
        "wire_fused_per_dev": hc.coll_wire_fused}
    del text

    chips = int(jnp.prod(jnp.asarray(mesh.devices.shape)))
    mf = analysis.model_flops(cfg, shape)
    rf = analysis.roofline_from_hlo(hc, chips, mf, fused_attention=True)
    out["roofline"] = rf.to_dict()
    out["roofline"]["bytes_flash_interior_per_dev"] = hc.bytes_flash_interior
    rf_unfused = analysis.roofline_from_hlo(hc, chips, mf,
                                            fused_attention=False)
    out["roofline_unfused_attention"] = {
        "memory_s": rf_unfused.memory_s,
        "dominant": rf_unfused.dominant,
        "roofline_fraction": rf_unfused.roofline_fraction}
    out["total_s"] = round(time.time() - t0, 1)
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} mesh={out['mesh']} "
              f"probes={probes}: compile {out['compile_s']}s, dominant="
              f"{rf.dominant}, terms=({rf.compute_s:.4f}, {rf.memory_s:.4f},"
              f" {rf.collective_s:.4f})s, roofline_frac="
              f"{rf.roofline_fraction:.3f}")
    return out


def _analytic_bytes(args, mesh) -> int:
    """Sum per-device bytes of all inputs (leaf bytes / shard count),
    assuming even sharding — the state-fits check for EXPERIMENTS.md."""
    total = 0
    for leaf in jax.tree.leaves(args):
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            total += int(jnp.dtype(leaf.dtype).itemsize *
                         max(1, jnp.prod(jnp.asarray(leaf.shape))
                             if leaf.shape else 1))
    return total  # global bytes; per-dev table derives in the report


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--probes", action="store_true")
    ap.add_argument("--probe-mode", default="scan")
    ap.add_argument("--out", default="results")
    args = ap.parse_args(argv)

    os.makedirs(args.out, exist_ok=True)
    cells = []
    if args.all:
        for arch in sorted(registry.ARCHS):
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        cells = [(args.arch, args.shape)]

    failures = 0
    for arch, shape in cells:
        tag = f"{arch}__{shape}__{'mp' if args.multi_pod else 'sp'}" + \
              ("__probes" if args.probes else "")
        path = os.path.join(args.out, tag + ".json")
        if os.path.exists(path):
            print(f"[dryrun] skip existing {tag}")
            continue
        try:
            res = run_cell(arch, shape, multi_pod=args.multi_pod,
                           probes=args.probes, probe_mode=args.probe_mode)
        except Exception as e:
            failures += 1
            res = {"arch": arch, "shape": shape, "error": str(e),
                   "traceback": traceback.format_exc()}
            print(f"[dryrun] FAIL {arch} x {shape}: {e}")
        with open(path, "w") as f:
            json.dump(res, f, indent=1)
    if failures:
        raise SystemExit(f"{failures} cell(s) failed")


if __name__ == "__main__":
    main()
