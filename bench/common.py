"""Helpers shared by the harness and the traffic job modules."""

from __future__ import annotations

import numpy as np

WARM_UP = 1 << 30  # job index of the warm-up, outside any window's range


def unit_seed(seed: int, i: int) -> int:
    """The seed of job ``i`` of a run: the same sequence for the same
    ``--seed``, inside 31 bits so that every JAX key accepts it."""
    state = np.random.SeedSequence([seed, i, 0x5EED]).generate_state(1)[0]
    return int(state & 0x7FFFFFFF)


def limit(name: str, value, bound) -> dict:
    """One compared number beside its limit (a number passes at or under
    it)."""
    ok = bool(value <= bound)
    if value != value or value in (float("inf"), float("-inf")):
        value = str(value)  # JSON has no infinity; the run is not correct
    return {"name": name, "value": value, "limit": bound, "ok": ok}


def relative(error, scale) -> float:
    """|error| / |scale|; a zero scale gives 0 for a zero error, else inf."""
    error, scale = abs(float(error)), abs(float(scale))
    if scale == 0.0:
        return 0.0 if error == 0.0 else float("inf")
    return error / scale


def worst(values) -> float:
    """The largest of ``values``, NaN counted as infinite; no values at all
    (no answer to compare) is infinite too."""
    values = [v if v == v else float("inf") for v in values]
    return max(values) if values else float("inf")
