"""The one generator of every traffic mix: a mix is a data file under
`traffic/`, read by name.

A mix states the batch and sequence length of the job, the probe lane,
and the probe programs as a deployment would load them: their eBPF text,
their maps, their attach targets, and each program's `reference` effect
(what `reference.expected_maps` says it computes). Token batches come
from `weights.make_batch` with the run's seed.

Keys of a mix file:
  batch, seq_len   the global batch of the train step
  probe_mode       the runtime's static probe lane ("fused", "scan", ...);
                   the programs are compiled into the step (attach mode
                   "fused")
  shm              the trainer joins a shm control plane (poll + publish)
  maps             [{name, kind, max_entries}]
  programs         [{name, text: [lines], maps, targets, reference}]
"""
from __future__ import annotations

import json
from pathlib import Path

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load(name: str) -> dict:
    path = TRAFFIC_DIR / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} ({path})")
    return json.loads(path.read_text())


def build_runtime(traffic: dict):
    """A `BpftimeRuntime` holding the mix's maps and programs, each attached
    to its targets in the fused lane."""
    from repro.core import maps as M
    from repro.core.runtime import BpftimeRuntime
    rt = BpftimeRuntime()
    specs = {m["name"]: M.MapSpec(m["name"], M.MapKind(m["kind"]),
                                  max_entries=m.get("max_entries", 64))
             for m in traffic.get("maps", [])}
    for prog in traffic.get("programs", []):
        pid = rt.load_asm(prog["name"], "\n".join(prog["text"]),
                          [specs[m] for m in prog["maps"]], "uprobe")
        for target in prog["targets"]:
            rt.attach(pid, target, promote=False, mode="fused")
    return rt
