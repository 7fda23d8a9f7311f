"""Device time of one admission in the traced part, in ms: the device
seconds of the programs named ``jit_prefill`` and ``jit_kv_scatter``
(the module's name up to its ``(``) over the engine's ``engine.prefill``
spans there, one per prefilled request.  A decode step that carries an
admission waits this long, so it sets the tail of the token gaps.  A
program without those names or spans reads nothing."""

PREFILL_SPAN = "engine.prefill"
PROGRAMS = ("jit_prefill", "jit_kv_scatter")


def read(run):
    n = run.trace.span_count(PREFILL_SPAN) if run.trace else 0
    seconds = [p.seconds for name, p in run.trace.programs.items()
               if name.split("(")[0] in PROGRAMS] if n else []
    if not seconds:
        return None
    return 1e3 * sum(seconds) / n
