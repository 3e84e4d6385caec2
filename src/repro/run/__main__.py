"""``python -m repro.run``: the declarative launcher.

Examples:
  PYTHONPATH=src python -m repro.run --preset lenet5 --backend local \
      --rounds 5 --sparsity 0.01
  PYTHONPATH=src python -m repro.run --preset fed-tiny --backend fed \
      --clients 8 --cohort 4 --rounds 3 --fast
  PYTHONPATH=src python -m repro.run --spec-json experiments/specs/my_run.json
"""
from __future__ import annotations

import json
import os
import time

import jax

from repro.paths import use_compile_cache
from repro.run.build import build_run
from repro.run.flags import build_parser, spec_from_args


def main(argv=None):
    use_compile_cache()
    args = build_parser().parse_args(argv)
    spec = spec_from_args(args)
    run = build_run(spec)

    n_params = sum(
        x.size for x in jax.tree.leaves(
            jax.eval_shape(run.model.init, jax.random.PRNGKey(0))
        )
    )
    clients = getattr(run, "n_clients", 0) or spec.clients
    print(
        f"run: backend={spec.backend} preset={spec.preset} "
        f"arch={run.cfg.name} params={n_params/1e6:.2f}M "
        f"compressor={spec.compressor} clients={clients} "
        f"delay={spec.delay} p={spec.sparsity} fast={spec.fast}"
    )
    t0 = time.time()
    state, hist = run.run(log_every=args.log_every)
    dt = time.time() - t0
    print(
        f"done in {dt:.1f}s: loss {hist['loss'][0]:.4f} → {hist['loss'][-1]:.4f}"
    )
    if "compression_rate" in hist:
        print(
            f"upload {hist['total_upload_bits']/8e6:.2f} MB/client  "
            f"compression ×{hist['compression_rate']:.0f}"
        )
    if run.channel is not None and run.ledger.records:
        t = run.ledger.totals()
        print(
            f"wire: up {t['up_bytes']/1e3:.1f} kB, down {t['down_bytes']/1e3:.1f} kB "
            f"(measured/analytic up "
            f"×{t['up_bits_measured']/max(t['up_bits_analytic'],1):.3f})"
        )
    if spec.telemetry:
        from repro.obs import finish_run

        finish_run(
            run.telemetry, trace=args.trace, metrics_out=args.metrics_out,
            meta={"backend": spec.backend, "preset": spec.preset,
                  "rounds": spec.rounds},
        )
    if args.history:
        os.makedirs(os.path.dirname(os.path.abspath(args.history)), exist_ok=True)
        with open(args.history, "w") as f:
            json.dump({k: v for k, v in hist.items() if k != "eval"}, f,
                      default=float)
        print(f"wrote {args.history}")
    return hist


if __name__ == "__main__":
    main()
