"""The largest scaled RMS error of a window call's potential (the number
`correct` compares, `reference.scaled_rms`): the accuracy that the fused
evaluate's far field (order p, theta) and near field give, read so that a
drift shows below the limit."""


def read(run):
    return max(run.errors.values()) if run.errors else None
