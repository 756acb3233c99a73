"""A ratio of sums of the PROGRAM's counters (the metrics registry), each
taken as its growth over the window. ``num`` and ``den`` map counter names to
weights; ``den_times`` multiplies the denominator by a fact of the run
(``rows``: the fused decode width); ``percent`` scales by 100; ``require``
lists counters that have to exist for there to be a reading at all."""

from ._common import window_counters


def _sum(weights, start, end):
    return sum(w * (end.get(k, 0.0) - start.get(k, 0.0))
               for k, w in weights.items())


def read(params, facts):
    marks = window_counters(facts, params.get("require", ()))
    if marks is None:
        return None
    start, end = marks
    den = _sum(params["den"], start, end)
    if params.get("den_times"):
        den *= facts["window"][params["den_times"]]
    if not den:
        return None
    return (100.0 if params.get("percent") else 1.0) * \
        _sum(params["num"], start, end) / den
