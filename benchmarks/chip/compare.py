"""The numbers that decide ``correct``, each against the reference.

Training (each a relative gap, the worse the larger):
  ``loss``    max over the compared steps of |L_prog - L_ref| / |L_ref|.
  ``grad``    the first gradient as the optimizer took it (after clipping),
              per leaf: | |g_prog| - |g_ref| | / max(|g_ref|, median leaf
              |g_ref|), worst leaf.
  ``change``  the same for each leaf's change after the compared steps,
              leaving out leaves whose reference gradient is under a
              thousandth of the median leaf's (such a leaf moves under Adam
              by rounding alone).
Serving:
  ``logit_gap``  the widest gap by which a served (greedy) token's logit
              lies below the reference's best at that position.

Layer-stacked leaves count layer by layer. A check is
``{"value", "limit", "ok"}``; ``ok`` means value <= limit.
"""
from __future__ import annotations

import statistics


def check(value: float, limit: float) -> dict:
    value = float(value)
    return {"value": value, "limit": float(limit), "ok": value <= limit}


def loss_gap(prog_losses, ref_losses) -> float:
    return max(abs(p - r) / abs(r) for p, r in zip(prog_losses, ref_losses))


def leaf_gaps(prog: dict, ref: dict, keep=None) -> dict:
    """Per leaf, | |prog| - |ref| | over max(|ref|, median leaf |ref|)."""
    if set(prog) != set(ref):
        raise ValueError(f"leaves differ: {sorted(set(prog) ^ set(ref))[:5]}")
    med = statistics.median(ref.values())
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in (ref if keep is None else keep)}


def norm_gap(prog: dict, ref: dict, keep=None) -> float:
    """The worst leaf's gap (``leaf_gaps``)."""
    return max(leaf_gaps(prog, ref, keep).values())


def worst_leaves(prog: dict, ref: dict, keep=None, n: int = 3) -> list:
    """The ``n`` worst leaves: (leaf, gap, |prog|, |ref|)."""
    gaps = leaf_gaps(prog, ref, keep)
    return [(k, gaps[k], prog[k], ref[k]) for k in sorted(gaps, key=gaps.get, reverse=True)[:n]]


def moving_leaves(ref_grad: dict) -> list:
    """Leaves whose reference gradient is at least a thousandth of the
    median leaf's."""
    med = statistics.median(ref_grad.values())
    return [k for k, g in ref_grad.items() if g >= 1e-3 * med]


def report_worst(prog: dict, ref: dict, out) -> None:
    """The worst leaves of the gradient and change comparisons, one line each."""
    for name, keep in (("grad", None), ("change", moving_leaves(ref["grad"]))):
        for k, gap, p, r in worst_leaves(prog[name], ref[name], keep):
            print(f"worst {name} leaf {k}: gap {gap:.4g}, program {p:.6g}, reference {r:.6g}",
                  file=out)


def train_numbers(prog: dict, ref: dict) -> dict:
    """prog/ref: {"losses": [...], "grad": {leaf: norm}, "change": {leaf: norm}}."""
    return {"loss": loss_gap(prog["losses"], ref["losses"]),
            "grad": norm_gap(prog["grad"], ref["grad"]),
            "change": norm_gap(prog["change"], ref["change"], moving_leaves(ref["grad"]))}
