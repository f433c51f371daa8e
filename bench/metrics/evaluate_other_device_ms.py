"""Device busy milliseconds per window call outside the P2P kernel:
upward pass, M2L, L2P, scatter and whatever the update puts on the
device, as one number until the program names its phases."""

KERNEL = "_p2p_stream"       # the stream kernel's HLO op in the trace


def read(run):
    if run.trace is None:
        return None
    import trace_reduce
    p2p = trace_reduce.op_seconds(run.trace, KERNEL)
    return (run.trace["busy_s"] - p2p) / run.calls * 1e3
