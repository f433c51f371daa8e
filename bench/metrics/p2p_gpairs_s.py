"""Useful near-field pair interactions per device second of the P2P
kernel, in 10^9 pairs/s.  Useful pairs are sum(n_tgt * n_src) over the
geometry plan's P2P leaf pairs, counted here from the plan: the same work
whatever kernel computes it (padding and dead lanes are not counted)."""

KERNEL = "_p2p_stream"       # the stream kernel's HLO op in the trace


def prepare(session):
    useful = 0
    for r in session.geometry.receivers:
        if r is None:
            continue
        for plan in [r.local] + [rb.inter for rb in r.remote]:
            for b in plan.p2p_blocks:
                useful += int((b.t_valid[:b.n].sum(axis=1)
                               * b.s_valid[:b.n].sum(axis=1)).sum())
    return useful


def read(run):
    if run.trace is None:
        return None
    import trace_reduce
    s = trace_reduce.op_seconds(run.trace, KERNEL)
    if s <= 0:
        return None
    return run.prepared["p2p_gpairs_s"] * run.calls / s / 1e9
