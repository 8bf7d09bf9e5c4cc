"""\"Kernel-mode\" probe baseline — the analogue of kernel uprobes.

Events cross the device->host boundary via io_callback (the int3 trap +
double context switch of the paper), execute in the reference interpreter
on host numpy maps, and the device waits. This is the baseline bpftime
beats by 10x; `tests/test_runtime.py` runs it over a collected tape into
the host maps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import vm
from .events import EVENT_WIDTH


def host_probe_stage(runtime, event_rows, step):
    """Insert a host round-trip probe-execution into a traced step.

    event_rows: traced i64[N, 16]. Side effects land in runtime.host_maps.
    Returns a token to thread (forces ordering).
    """
    attach = sorted(runtime.device_attach.items())
    progs = {pid: runtime.progs[pid] for _, pids in attach for pid in pids}

    def host_fn(rows_np, step_np):
        rows_np = np.asarray(rows_np)
        for (sid, kind), pids in attach:
            mask = (rows_np[:, 0] == sid) & (rows_np[:, 1] == kind)
            for pid in pids:
                p = progs[pid]
                for row in rows_np[mask]:
                    row = row.copy()
                    row[3] = int(step_np)
                    ctx = vm.pack_ctx([int(x) for x in row])
                    vm.run(p.insns, ctx, runtime.map_specs,
                           runtime.host_maps,
                           vm.Aux(time_ns=int(step_np), pid=runtime.syscalls.pid))
        return np.int64(rows_np.shape[0])

    return jax.experimental.io_callback(
        host_fn, jax.ShapeDtypeStruct((), jnp.int64),
        event_rows, step, ordered=True)
