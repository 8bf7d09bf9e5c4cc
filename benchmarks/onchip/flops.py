"""Operations and bytes the work needs, from the configuration's shapes.

Model FLOPs per trained token are the architecture module's
(`flops_per_token`, `arch/`). What is generic is here: the probe sites a
traffic mix attaches to, and the stats bytes of one step, every probed
tensor read once at its own dtype, whatever implements the read, summed
from the architecture's bytes of each site (`site_bytes`).
"""
from __future__ import annotations

KIND = {"uprobe": 0, "uretprobe": 1, "probe": 2}


def probed_sites(traffic: dict) -> set:
    """(site, kind) pairs that some program of the traffic attaches to."""
    return {(t.split(":")[1], KIND[t.split(":")[0]])
            for p in traffic.get("programs", []) for t in p["targets"]}


def stats_bytes_per_step(arch, model: dict, traffic: dict) -> int:
    """Bytes the stats of one step must read: each probed site once per
    event, at every layer where it fires (`arch.site_bytes`); a site that
    fires nowhere in the model reads nothing."""
    sizes = arch.site_bytes(model, traffic["batch"], traffic["seq_len"])
    return sum(sizes.get(site, 0) for site in probed_sites(traffic))
