"""Device idle per decode step in the traced part, in ms: the window's
idle time (``window_s - busy_s``) over the engine's ``engine.dispatch``
spans there, one per decode step.  It is the host's share of the token
gap: readback, emitting, preparing the next step's inputs, admission
and prefill syncs.  A program without the engine's spans reads
nothing."""

DISPATCH_SPAN = "engine.dispatch"


def read(run):
    n = run.trace.span_count(DISPATCH_SPAN) if run.trace else 0
    if not n:
        return None
    return 1e3 * (run.trace.window_s - run.trace.busy_s) / n
