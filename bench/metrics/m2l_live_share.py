"""Share of the pair rows the M2L loop runs that are live interactions:
`m2l.pairs_live / m2l.pairs_padded`, the program's counters of one fused
launch (`DeviceEngine`; the padding is the table's power-of-two envelope
and the loop's last chunk)."""

import call_counters


def prepare(session):
    c = call_counters.counters(session)
    if not c.get("m2l.pairs_padded"):
        return None
    return c["m2l.pairs_live"] / c["m2l.pairs_padded"]


def read(run):
    return run.prepared.get("m2l_live_share")
