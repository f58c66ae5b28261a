"""Share of its roofline the dist mgpmh sweep reaches: the least time of
one call by ``bench/cost.py`` and ``bench/peaks.json``, over the device
time of every program the window ran, per call (the dist sweep, and the
benchmark's copies of the few states it keeps, a few microseconds each)."""
from bench.cost import roofline_share


def read(rec, red):
    s = sum(red["modules"].values())
    if s <= 0 or not rec["calls"]:
        return None
    return roofline_share(rec, s / rec["calls"])
