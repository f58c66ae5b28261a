"""Share of its roofline the fused mgpmh kernel reaches: the least time of
one call by ``bench/cost.py`` and ``bench/peaks.json``, over the kernel's
device time per call (its Pallas custom call, ``mgpmh_sweep_pallas``)."""
from bench.cost import roofline_share


def read(rec, red):
    s = sum(sec for _, op, sec in red["ops"]
            if op.startswith("mgpmh_sweep_pallas"))
    if s <= 0 or not rec["calls"]:
        return None
    return roofline_share(rec, s / rec["calls"])
