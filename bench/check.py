"""Decide ``correct``: every answer the timed path returned in the window
against the configuration's plain reference.

Each check of the configuration (``checks`` in its file) names the
answers it covers, as ``<query>/<output name>``, and its limit.  Its
number is the largest absolute gap, over every request of the window,
between an answer and the reference's.  ``missing_answers`` counts the
answers that failed, never came, lack an output or are not one number;
its limit is 0.
"""

from __future__ import annotations

import math

import numpy as np


def _gap(got, want) -> float | None:
    """The absolute gap, or None for an answer that is not one number."""
    g = np.asarray(got)
    if g.shape != ():
        return None
    g, w = float(g), float(np.asarray(want))
    if math.isnan(g):
        return None
    return abs(g - w)


def compare(checks: dict, want: dict[str, dict], answers
            ) -> tuple[dict, int]:
    """``answers``: ``(query name, values dict or exception)`` pairs, one
    per answer due in the window.  Returns ``{check: {"value", "limit"}}``,
    with ``missing_answers`` last, and how many answers failed: missing,
    or beyond the limit of a check."""
    worst = {name: 0.0 for name in checks}
    owner = {}
    for name, c in checks.items():
        for a in c["answers"]:
            owner[a] = name
    missing = failed = 0
    for query, values in answers:
        if isinstance(values, BaseException) or values is None:
            missing += 1
            failed += 1
            continue
        bad = False
        for out, w in want[query].items():
            key = f"{query}/{out}"
            gap = _gap(values[out], w) if out in values else None
            if gap is None:
                missing += 1
                bad = True
            elif key in owner:
                c = owner[key]
                worst[c] = max(worst[c], gap)
                bad |= gap > checks[c]["limit"]
        failed += bad
    out = {name: {"value": worst[name], "limit": checks[name]["limit"]}
           for name in checks}
    out["missing_answers"] = {"value": missing, "limit": 0}
    return out, failed


def passed(numbers: dict) -> bool:
    return all(v["value"] <= v["limit"] for v in numbers.values())


def lines(numbers: dict) -> list[str]:
    return [f"check {k}: {v['value']!r} (limit {v['limit']!r})"
            for k, v in numbers.items()]
