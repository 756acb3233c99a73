"""Growth of one of the PROGRAM's counters (the metrics registry) over the
window, a second of the window's wall clock: ``counter`` names it, ``scale``
multiplies the rate (a counter of milliseconds at ``scale`` 0.1 reads the
share of the window in %), ``require`` lists counters that have to exist for
there to be a reading at all."""

from . import counter_delta


def read(params, facts):
    grown = counter_delta.read({**params, "source": "program"}, facts)
    if grown is None:
        return None
    return params.get("scale", 1.0) * grown / facts["window"]["seconds"]
