"""Quantile ``q`` of one of the program's histograms over the window only:
the runner reads ``count_le`` on a geometric ladder of values when the window
opens and when it closes, and the quantile is taken from the difference
(geometric midpoint of the ladder step that holds it)."""

import math

from runners.serve import LADDER


def read(params, facts):
    marks = facts["window"].get("marks")
    if not marks or "start" not in marks:
        return None
    name = params["histogram"]
    a, b = marks["start"]["ladders"][name], marks["end"]["ladders"][name]
    counts = [y - x for x, y in zip(a, b)]
    if not counts or counts[-1] <= 0:
        return None
    target = params["q"] * counts[-1]
    for i, c in enumerate(counts):
        if c >= target:
            lo = LADDER[i - 1] if i else LADDER[0] / 2
            return math.sqrt(lo * LADDER[i])
    return float(LADDER[-1])
