"""The comparison that decides ``correct``, and its control.

A query's exact answer is the number of embeddings the reference lists
(``reference/<query>.py`` gives one value, 1, per embedding). Counts are
integers, so the limit on the gap is 0.

The control is that reference computed one precision lower: the values
summed in bfloat16 on the default device. It breaks the exactness the
configurations state, and the comparison has to call it wrong.
"""
from __future__ import annotations

import math

import numpy as np

ENTRIES = ("count",)


def exact_answer(values: np.ndarray) -> int:
    return int(values.size)


def control_answer(values: np.ndarray) -> float:
    import jax.numpy as jnp
    v = jnp.asarray(values, dtype=jnp.bfloat16)
    return float(jnp.sum(v, dtype=jnp.bfloat16)) if v.size else 0.0


def answer_gap(answers: list, want) -> float:
    """The widest gap between an answer and the reference; inf where an
    answer is missing or not a number."""
    gap = 0.0
    for a in answers:
        try:
            d = abs(float(a) - float(want))
        except (TypeError, ValueError):
            return math.inf
        gap = max(gap, d if d == d else math.inf)
    return gap
