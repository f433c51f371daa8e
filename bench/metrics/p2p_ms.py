"""Device milliseconds per window call of the streaming P2P kernel
(`kernels/p2p_stream.py`), summed from its ops in the profiler trace."""

KERNEL = "_p2p_stream"       # the stream kernel's HLO op in the trace


def read(run):
    if run.trace is None:
        return None
    import trace_reduce
    s = trace_reduce.op_seconds(run.trace, KERNEL)
    return s / run.calls * 1e3 if s > 0 else None
