"""End-to-end DSGD training launcher — a thin parser over ``repro.run``.

Runs the paper's training setting — M clients, communication delay n,
sparsity p, any registered compressor — on a synthetic-but-learnable task
sized by ``--preset`` (see :mod:`repro.run.presets`).  All flags are the
shared :func:`repro.run.add_run_flags` surface; this module only pins the
backend to "local", re-pins a few defaults, and keeps the two
launcher-specific extras (``--save``, ``--print-policy``).

Examples:
  PYTHONPATH=src python -m repro.launch.train --preset lm-100m \
      --compressor sbc --delay 10 --sparsity 0.01 --rounds 200
  PYTHONPATH=src python -m repro.launch.train --preset paper-lenet \
      --compressor topk --sparsity 0.001 --rounds 100
  PYTHONPATH=src python -m repro.launch.train --preset paper-lstm \
      --compressor sbc --sparsity 0.001 \
      --dense-pattern '(^|/)(bias|scale|norm[^/]*)(/|$)' --measure-wire
  PYTHONPATH=src python -m repro.launch.train --spec-json my_run.json
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax

from repro.checkpoint import save_pytree
from repro.core.baselines import dgc_policy  # noqa: F401 (registration)
from repro.paths import use_compile_cache
from repro.run.build import build_run, lr_schedule  # noqa: F401 (re-export)
from repro.run.flags import add_run_flags, spec_from_args
from repro.run.presets import build_preset, lm_100m_config  # noqa: F401


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    add_run_flags(
        ap,
        preset="lm-100m",
        backend="local",
        rounds=200,
        seq_len=256,
        log_every=10,
    )
    ap.add_argument("--save", default=None, help="checkpoint path (.npz)")
    ap.add_argument("--print-policy", action="store_true",
                    help="print the per-leaf codec resolution and exit")
    return ap


def main(argv=None):
    use_compile_cache()
    args = build_parser().parse_args(argv)
    spec = spec_from_args(args, backend="local")
    run = build_run(spec)

    if args.print_policy:
        a_params = jax.eval_shape(run.model.init, jax.random.PRNGKey(0))
        print(run.trainer.resolved(a_params).describe())
        return {}

    n_params = sum(
        x.size for x in jax.tree.leaves(
            jax.eval_shape(run.model.init, jax.random.PRNGKey(0))
        )
    )
    print(
        f"preset={spec.preset} arch={run.cfg.name} params={n_params/1e6:.1f}M "
        f"compressor={spec.compressor} clients={spec.clients} "
        f"delay={spec.delay} p={spec.sparsity}"
    )
    t0 = time.time()
    state, hist = run.run(log_every=args.log_every)
    dt = time.time() - t0
    print(
        f"done in {dt:.1f}s: loss {hist['loss'][0]:.4f} → {hist['loss'][-1]:.4f}  "
        f"upload {hist['total_upload_bits']/8e6:.2f} MB/client  "
        f"compression ×{hist['compression_rate']:.0f}"
    )
    if spec.measure_wire:
        print(
            f"measured wire: {hist['measured_total_bits']/8e6:.2f} MB/client "
            f"(analytic {hist['total_upload_bits']/8e6:.2f} MB)"
        )
    if spec.telemetry:
        from repro.obs import finish_run

        finish_run(
            run.telemetry, trace=args.trace, metrics_out=args.metrics_out,
            meta={"backend": "local", "preset": spec.preset,
                  "rounds": spec.rounds},
        )
    if args.save:
        save_pytree(args.save, state.params)
        print(f"saved params to {args.save}")
    if args.history:
        os.makedirs(os.path.dirname(os.path.abspath(args.history)), exist_ok=True)
        with open(args.history, "w") as f:
            json.dump({k: v for k, v in hist.items() if k != "eval"}, f)
    return hist


if __name__ == "__main__":
    main()
