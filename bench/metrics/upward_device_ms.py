"""Device milliseconds per window call of the upward pass (P2M at the
leaves, M2M up each tree): the trace's ops that the program puts in phase
`fmm.upward` (`FMMSession.device_phases`)."""

import phase_time

PHASE = "fmm.upward"


def prepare(session):
    return phase_time.phase_ops(session, PHASE)


def read(run):
    return phase_time.ms_per_call(run, "upward_device_ms")
