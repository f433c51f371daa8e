"""The largest relative L2 error of a window call's potential on the
checked targets against the float64 reference, the accuracy measure FMM
papers report.  Not compared: on 2,000 sampled targets it is decided by
the few with the nearest neighbours (`reference.py`)."""


def read(run):
    return max(run.rel_l2.values()) if run.rel_l2 else None
