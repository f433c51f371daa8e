"""Device milliseconds per window call of the downward sweep (L2L), the
leaf evaluation (L2P) and its add into the potentials: the trace's ops
that the program puts in phase `fmm.downward`
(`FMMSession.device_phases`)."""

import phase_time

PHASE = "fmm.downward"


def prepare(session):
    return phase_time.phase_ops(session, PHASE)


def read(run):
    return phase_time.ms_per_call(run, "downward_device_ms")
