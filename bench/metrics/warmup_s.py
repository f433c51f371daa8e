"""Seconds of the warm-up by the host clock: the cold `evaluate()`
(compile or cache load of the fused executable, the P2P autotune sweep or
its cache) and one warm-up call of the mix."""


def read(run):
    return run.warmup_s
