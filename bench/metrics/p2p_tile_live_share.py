"""Share of the stream kernel's grid tiles that hold near-field work:
`p2p.stream.tiles / p2p.stream.grid_tiles`, the program's counters of
one launch (the rest pad the tile table to its power-of-two envelope, and
their lanes are still added into the potentials)."""

import call_counters


def prepare(session):
    c = call_counters.counters(session)
    if not c.get("p2p.stream.grid_tiles"):
        return None
    return c["p2p.stream.tiles"] / c["p2p.stream.grid_tiles"]


def read(run):
    return run.prepared.get("p2p_tile_live_share")
