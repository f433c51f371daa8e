"""The program's counters over one call of the matvec mix, for the
readers of `program_counter` metrics.

The harness switches `repro.obs` off in every run, so the window counts
nothing.  After the window, with the trace stopped, `counters` switches
it on for one more call as the mix makes them (the same positions, fresh
charges through `FMMSession.step`, then `evaluate()`) and returns how far
each counter moved.  What the readers take from it is fixed by the plan
and the route of the call: the same in every call of the window.  The
call runs once per session, whichever reader asks first."""
import weakref

_SEEN = weakref.WeakKeyDictionary()


def counters(session) -> dict:
    """{counter: increase} over one matvec call of `session`."""
    if session not in _SEEN:
        from repro import obs
        geo = session.geometry
        obs.configure(enabled=True)
        try:
            before = dict(obs.metrics_snapshot()["counters"])
            session.step(geo.x0, -geo.q0)
            session.evaluate()
            after = obs.metrics_snapshot()["counters"]
        finally:
            obs.configure(enabled=False)
        _SEEN[session] = {k: v - before.get(k, 0.0)
                          for k, v in after.items()}
    return _SEEN[session]
