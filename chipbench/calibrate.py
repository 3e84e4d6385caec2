"""Readings that a cell's limits are set from, in one process on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1 2 ... \\
        --control-seeds 1 2 3 --out <cell>.calib.jsonl

For every seed it drives the program through the same set-up rounds a
benchmark run does and compares them with the reference, as ``correct``
does (the lower readings).  For each control seed it also puts in the
program's place the reference computed in bfloat16 (the control) and the
reference with each fault planted that the cell can have: half of every
batch left out, the exchange left out (several clients only), one leaf's
update doubled in every round where it is produced.  Their numbers are the upper
readings.  A state left unchanged reads 1 on ``update_norm_gap`` by
construction and needs no run.  One JSON line per reading.
"""
import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax.numpy as jnp

    from chipbench import harness
    from chipbench.check import compare

    cell = harness.load_cell(args.workload)
    devices = harness.chip_devices(cell.chips)
    harness.use_compile_cache()
    prog = harness.Program(cell, args.seeds[0])
    kinds = [("control", jnp.bfloat16, None), ("half_batch", None, "half_batch"),
             ("scaled_update", None, "scaled_update")]
    if cell.traffic["clients"] > 1:
        kinds.append(("no_exchange", None, "no_exchange"))
    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a") as f:
        def emit(row):
            f.write(json.dumps(row) + "\n")
            f.flush()
            log(json.dumps(row))

        for seed in args.seeds:
            su = harness.setup_rounds(prog, seed)
            su.state = None
            gc.collect()
            ref = harness.reference_evidence(cell, seed, devices[0])
            values = harness.check_numbers(cell, su, ref, log)
            emit({"cell": cell.name, "seed": seed, "kind": "program",
                  "counters": su.counters, **values,
                  "evidence": harness.evidence_json(su.evidence, ref)})
            del su
            if seed not in args.control_seeds:
                continue
            for kind, dtype, fault in kinds:
                ev = harness.reference_evidence(cell, seed, devices[0], dtype, fault)
                values, _ = compare.numbers(ev, ref)
                emit({"cell": cell.name, "seed": seed, "kind": kind, **values,
                      "evidence": harness.evidence_json(ev, ref)})
                gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
