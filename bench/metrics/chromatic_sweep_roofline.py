"""Share of its roofline the chromatic sweep over neighbour lists reaches:
the least time of one call by ``bench/cost_chromatic.py`` and
``bench/peaks.json``, over the device time of its program
(``jit_gibbs_chromatic_sweep``) per call.  None where the window ran no
such program."""
from bench.cost_chromatic import roofline_share


def read(rec, red):
    s = red["modules"].get("jit_gibbs_chromatic_sweep", 0.0)
    if s <= 0 or not rec["calls"]:
        return None
    return roofline_share(rec, s / rec["calls"])
