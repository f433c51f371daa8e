"""Device milliseconds per window call of the M2L loop, which runs every
pair row of the padded table (`engine/m2l.py`, `m2l_accumulate`): the
trace's ops that the program puts in phase `fmm.m2l`
(`FMMSession.device_phases`)."""

import phase_time

PHASE = "fmm.m2l"


def prepare(session):
    return phase_time.phase_ops(session, PHASE)


def read(run):
    return phase_time.ms_per_call(run, "m2l_device_ms")
