"""Sweep chunks the pool ran inside ``submit`` per query answered ``ok``:
the pool's own ``sweep_chunk`` spans on the client's thread during the
window (the chunks its freshness gate asked for; the background driver
runs its chunks on another thread)."""


def read(rec, red):
    if not rec["answered_ok"] or not rec["spans"]:
        return None
    t0, t1 = rec["span_window_us"]
    chunks = sum(e.get("args", {}).get("chunks", 1) for e in rec["spans"]
                 if e.get("name") == "sweep_chunk" and e.get("ph") == "X"
                 and e.get("tid") == rec["client_tid"]
                 and t0 <= e["ts"] <= t1)
    return chunks / rec["answered_ok"]
