"""Comparisons shared by the drivers' correctness checks."""

from __future__ import annotations

import numpy as np


def norm_gap(got: dict, want: dict) -> float:
    """Worst leaf of |‖got‖ − ‖want‖| over the larger of that leaf's and the
    median leaf's reference norm (``got``/``want``: leaf name → norm).

    Leaves whose reference norm is under a thousandth of the median leaf's
    move by round-off alone and are left out."""
    med = float(np.median(list(want.values())))
    gaps = [abs(got[n] - w) / max(w, med) for n, w in want.items()
            if w >= 1e-3 * med and max(w, med) > 0]
    return max(gaps) if gaps else float("inf")
