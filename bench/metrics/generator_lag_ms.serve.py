"""95th percentile, in ms, of how late each query reached
``ChainPool.submit`` after it was due: the client's own lag, which the
query latency includes.  A starved client reads here, not as a slow
pool."""
import numpy as np


def read(rec, red):
    lags = np.sort(np.asarray(rec["lags_s"], np.float64))
    if lags.size == 0:
        return None
    k = int(np.ceil(0.95 * lags.size)) - 1
    return 1e3 * float(lags[min(max(k, 0), lags.size - 1)])
