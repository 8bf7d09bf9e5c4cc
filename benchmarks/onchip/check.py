"""The comparison that decides `correct`.

Each number is compared with its limit from `limits/<cell>.json`:

  loss_gap     worst of the first three steps: |program loss - reference
               loss| / reference loss
  grad_gap     worst leaf: | |g1| - |g1 ref| | / max(|g1 ref|, median leaf
               |g1 ref|), g1 the first clipped gradient, read back from the
               optimizer's first moment after one step
  change_gap   worst leaf: | |dp| - |dp ref| | / max(|dp ref|, median leaf
               |dp ref|), dp the parameters' change over the three steps;
               leaves whose reference gradient is under a thousandth of the
               median leaf's are left out (they move by round-off alone)
  count_diff   probe maps after three steps against the reference's
               events: every ARRAY and HASH count, each histogram's total
               and the vetoed steps, summed absolute difference (exact: 0)
  hist_moves   events that fell in another log2 bin than the reference's
  rms_gap      the stats kernel at every tensor probe site and layer,
  mean_gap     from the `sum` map after three steps against the
  extreme_gap  reference's sums: worst |program - reference| over the sum
               of rms (rms_gap, mean_gap) or of absmax (extreme_gap: min,
               max and absmax); the summed NaN and Inf counts join
               count_diff, exactly
  scalar_gap   the same for the one-element sites (the loss and the
               gradient norm as the probe reads them), over |value|
  window_count_diff  probe maps after the window: every count and
               histogram total against steps x the reference's per-step
               events (exact: 0)
"""
from __future__ import annotations

import math

import numpy as np

import reference as R

EXACT = ("count_diff", "window_count_diff")
ROUNDOFF_GRAD = 1e-3


def _gap(prog: dict, ref: dict, leaves) -> float:
    med = float(np.median([ref[k] for k in leaves]))
    worst = 0.0
    for k in leaves:
        p = prog.get(k, math.nan)
        if not math.isfinite(p):
            return math.inf
        worst = max(worst, abs(p - ref[k]) / max(ref[k], med, 1e-30))
    return worst


def model_numbers(prog: dict, ref: dict) -> dict:
    losses = [(p, r) for p, r in zip(prog["loss"], ref["loss"])]
    if len(losses) < len(ref["loss"]) or \
            not all(math.isfinite(p) for p, _ in losses):
        loss_gap = math.inf
    else:
        loss_gap = max(abs(p - r) / abs(r) for p, r in losses)
    g = ref["grad1"]
    med = float(np.median(list(g.values())))
    moving = [k for k, v in g.items() if v >= ROUNDOFF_GRAD * med]
    return {"loss_gap": loss_gap,
            "grad_gap": _gap(prog["grad1"], g, list(g)),
            "change_gap": _gap(prog["change"], ref["change"], moving)}


def _count_diff(kind: str, got: dict, want: dict, site_name) -> float:
    """Summed |program - expected| over one count map."""
    if kind == "array":
        vals = got["values"]
        return float(sum(abs(int(vals[i]) - want.get(i, 0))
                         for i in range(len(vals)))
                     + sum(c for i, c in want.items() if i >= len(vals)))
    seen, diff = set(), 0
    for k, u, v in zip(got["keys"], got["used"], got["values"]):
        if u != 1:
            continue
        try:
            key = (site_name(int(k) >> 8), int(k) & 255)
        except IndexError:              # a site id the program never made
            key = (None, int(k))
        diff += int(v) if key in seen else abs(int(v) - want.get(key, 0))
        seen.add(key)
    return float(diff + sum(c for k, c in want.items() if k not in seen))


# fields of a `sum` map, in the order of the traffic's `fields`
MEAN, RMS, MIN, MAX, ABSMAX, NONFINITE = range(6)


def stats_numbers(got: dict, want: dict, site_name) -> dict:
    """The sums a `sum` program left, per (site, layer, field), against
    the reference's: relative gaps of the statistics, and the summed
    |difference| of the non-finite counts plus one for each key that only
    one side has."""
    have = {}
    for k, u, v in zip(got["keys"], got["used"], got["values"]):
        if u == 1:
            k = int(k)
            try:
                have[(site_name(k >> 11), (k >> 3) & 255, k & 7)] = int(v)
            except IndexError:          # a site id the program never made
                have[(None, k, None)] = int(v)
    gaps = {"rms_gap": 0.0, "mean_gap": 0.0, "extreme_gap": 0.0,
            "scalar_gap": 0.0}
    diff = float(len(set(have) ^ set(want)))
    for (site, layer, field), w in want.items():
        p = have.get((site, layer, field), 0)
        if field == NONFINITE:
            diff += abs(p - w)
            continue
        scale = want[(site, layer, ABSMAX if field in (MIN, MAX, ABSMAX)
                      else RMS)]
        if site in R.SCALAR_SITES:
            name = "scalar_gap"
        else:
            name = {MEAN: "mean_gap", RMS: "rms_gap"}.get(field,
                                                         "extreme_gap")
        gaps[name] = max(gaps[name], abs(p - w) / max(abs(scale), 1))
    return {**gaps, "count_diff": diff}


def map_numbers(prog: dict, ref: dict, traffic: dict) -> dict:
    kinds = {m["name"]: m["kind"] for m in traffic["maps"]}
    programs = traffic["programs"]
    sums = {p["reference"]["map"] for p in programs
            if p["reference"]["op"] == "sum"}
    exp = R.expected_maps(programs, ref["sites"])
    per_step = R.expected_maps(programs, ref["sites"][:1])["maps"]
    n = prog["total_steps"]
    count_diff = float(abs(sum(prog["vetoed"]) - exp["vetoed"]))
    moves = window = 0.0
    out = {}
    for name, kind in kinds.items():
        got, fin = prog["maps"][name], prog["final_maps"][name]
        want = exp["maps"].get(name, {})
        if name in sums:
            # the reference follows the first steps only: the sums after
            # the window have nothing to be compared with
            numbers = stats_numbers(got, want, prog["site_name"])
            count_diff += numbers.pop("count_diff")
            out.update({k: max(v, out.get(k, 0.0))
                        for k, v in numbers.items()})
        elif kind == "log2hist":
            bins = np.asarray(got["bins"], np.int64)
            w = np.zeros(len(bins), np.int64)
            for b, c in want.items():
                w[b] += c
            total = abs(int(bins.sum()) - int(w.sum()))
            count_diff += total
            moves += (np.abs(bins - w).sum() - total) / 2
            window += abs(int(np.asarray(fin["bins"]).sum())
                          - n * sum(per_step.get(name, {}).values()))
        else:
            count_diff += _count_diff(kind, got, want, prog["site_name"])
            window += _count_diff(kind, fin, {k: n * c for k, c in
                                              per_step.get(name, {}).items()},
                                  prog["site_name"])
    return {"count_diff": count_diff, "hist_moves": float(moves), **out,
            "window_count_diff": float(window)}


def compare(prog: dict, ref: dict, traffic: dict, limits: dict) -> dict:
    """{number: {"value", "limit"}} for every number the cell compares."""
    numbers = model_numbers(prog, ref)
    if traffic.get("programs"):
        numbers.update(map_numbers(prog, ref, traffic))
    return {k: {"value": v, "limit": 0.0 if k in EXACT else limits[k]}
            for k, v in numbers.items()}
