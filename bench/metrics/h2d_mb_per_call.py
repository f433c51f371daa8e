"""Megabytes (10^6 B) copied from host to device per call of the mix:
the program's counter `engine.h2d_bytes` (donated payload uploads, device
memo misses, the step's `new_x`) over one call."""

import call_counters


def prepare(session):
    c = call_counters.counters(session)
    if "engine.h2d_bytes" not in c:
        return None
    return c["engine.h2d_bytes"] / 1e6


def read(run):
    return run.prepared.get("h2d_mb_per_call")
