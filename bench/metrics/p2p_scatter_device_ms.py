"""Device milliseconds per window call of the add of the near-field
values (every lane of every stream tile, padding included) into the
potentials: the trace's ops that the program puts in phase
`fmm.p2p_scatter` (`FMMSession.device_phases`)."""

import phase_time

PHASE = "fmm.p2p_scatter"


def prepare(session):
    return phase_time.phase_ops(session, PHASE)


def read(run):
    return phase_time.ms_per_call(run, "p2p_scatter_device_ms")
