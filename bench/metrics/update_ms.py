"""Mean milliseconds of `FMMSession.step` per window call, by the host
clock (the session update layer); nothing to read where the mix updates
nothing."""


def read(run):
    if not run.update_s:
        return None
    return sum(run.update_s) / len(run.update_s) * 1e3
