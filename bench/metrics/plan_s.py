"""Seconds of `FMMSession.from_points`: partition, trees, LETs, receiver
plans and the device traversal (the plan layer), by the host clock."""


def read(run):
    return run.plan_s
