"""Executed by test_obs.py in a subprocess with 4 forced CPU devices.

The gspmd backend's round (one client per device, the shard_map exchange)
compiled for four devices: its stage map, per exchange path.  Prints one
CHECK line per path; the pytest wrapper asserts on them.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")  # forced devices are CPU-only

import json

from repro.paths import use_compile_cache

use_compile_cache()  # the suite's cache (conftest.py does the same)

from repro.run import RunSpec, build_run

PATHS = {
    "per_leaf": dict(fast=False),
    "flat": dict(fast=True),
    "flat_device_pack": dict(fast=True, device_pack=True),
}

for name, kw in PATHS.items():
    run = build_run(RunSpec(preset="lenet5", backend="gspmd", compressor="sbc",
                            sparsity=0.01, batch=4, **kw))
    m = run.stage_map(run.init(), 0)
    counts = {}
    for stage in m.values():
        counts[str(stage)] = counts.get(str(stage), 0) + 1
    print("CHECK", json.dumps({"path": name, "clients": run.n_clients,
                               "n": len(m), "stages": counts}), flush=True)
