"""Device time of one named phase of the fused evaluate, for the
`*_device_ms` readers.

The program names the phases of its fused launch (`jax.named_scope`:
`fmm.upward`, `fmm.m2l`, ...) and maps the launch's top-level HLO ops to
them (`FMMSession.device_phases()`); the profiler trace times those ops
under the same names (`trace_reduce`).  A program that names no phases
gives an empty map, and the readers then report nothing."""


def phase_ops(session, phase: str) -> set:
    """The top-level ops of the warm fused evaluate in `phase`."""
    phases = getattr(session, "device_phases", None)
    return {op for op, ph in (phases() if phases else {}).items()
            if ph == phase}


def ms_per_call(run, metric: str) -> float | None:
    """Device milliseconds per window call in the ops `prepare` found for
    `metric`, or None without a trace or without such ops."""
    ops = run.prepared.get(metric)
    if run.trace is None or not ops:
        return None
    s = sum(run.trace["ops"].get(op, 0.0) for op in ops)
    return s / run.calls * 1e3
