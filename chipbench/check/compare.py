"""The numbers that decide ``correct``; a cell compares those its
``limits`` name, each against its limit.

  loss_rel_gap       the worst of the first three rounds' |loss - ref| / |ref|;
  grad_norm_gap      the worst leaf's |g - g_ref| / max(g_ref, median g_ref),
                     g the norm of the first gradient as the optimizer holds
                     it after one round (Adam's sqrt(sum v));
  update_norm_gap    the same for the norm of the parameters' change over the
                     three rounds, leaving out leaves whose reference gradient
                     is under a thousandth of the median leaf's;
  support_gap        |s - s_ref| / s_ref for the number s of parameters that
                     the three rounds changed at all: a sparse update leaves
                     every unpicked weight exactly as it was, so weights held
                     or rounded in a lower precision change everywhere, and
                     other rows or another mean change other entries.
"""
import numpy as np

GRAD_FLOOR = 1e-3  # of the median leaf's gradient: round-off only below it


def leaf_gaps(got, ref, keep=None):
    """Per-leaf |got - ref| / max(ref, median ref) over the kept leaves
    (0 elsewhere), and the kept mask."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    if keep is None:
        keep = np.ones(ref.shape, bool)
    med = float(np.median(ref[keep]))
    denom = np.maximum(ref, med)
    diff = np.abs(got - ref)
    # a leaf that both sides hold at exactly zero agrees
    gaps = np.where(denom > 0, diff / np.where(denom > 0, denom, 1.0),
                    np.where(diff > 0, np.inf, 0.0))
    return np.where(keep, gaps, 0.0), keep


def numbers(prog, ref):
    """prog / ref: dicts with ``losses``, ``grad``, ``change`` and
    ``support`` (per leaf), and ``paths``.  Returns {name: value} and a
    note on the worst leaves."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    grad, _ = leaf_gaps(prog["grad"], ref["grad"])
    g_ref = np.asarray(ref["grad"], np.float64)
    upd, keep = leaf_gaps(prog["change"], ref["change"],
                          g_ref >= GRAD_FLOOR * np.median(g_ref))
    paths = ref["paths"]
    note = (f"worst grad leaf {paths[int(np.argmax(grad))]}, worst update leaf "
            f"{paths[int(np.argmax(upd))]}, {int((~keep).sum())} leaves left out of "
            "the update")
    support, support_ref = (float(np.sum(x["support"])) for x in (prog, ref))
    return {"loss_rel_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
            "grad_norm_gap": float(grad.max()), "update_norm_gap": float(upd.max()),
            "support_gap": abs(support - support_ref) / support_ref}, note


def verdict(values, limits):
    """(correct, [(name, value, limit)]) — every number at or under its limit."""
    rows = [(k, values[k], limits[k]) for k in limits if k in values]
    missing = [k for k in limits if k not in values]
    ok = not missing and all(np.isfinite(v) and v <= lim for _, v, lim in rows)
    return ok, rows
