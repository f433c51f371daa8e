"""Mean milliseconds of `FMMSession.evaluate()` per window call, by the
host clock, ended by the potential's copy to the host (the fused evaluate
layer)."""


def read(run):
    return sum(run.evaluate_s) / len(run.evaluate_s) * 1e3
