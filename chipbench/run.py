"""The chip benchmark's command.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell (``chipbench/workloads/<cell>.json``) on the machine it is
started on and prints, as the last line of standard output, one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``: each
number compared for ``correct`` beside its limit, which also close
standard error.  With no TPU, or fewer chips than the cell asks for, it
exits 3 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness

    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START, log=log)
    except harness.NoChip as e:
        log(f"chipbench: {e}; this benchmark runs only on the chip")
        return 3
    for name, row in result["checks"].items():
        log(f"check {name} = {row['value']!r} (limit {row['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
