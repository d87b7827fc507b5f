"""compiles.path: programs the process compiled, or loaded from the
persistent compile cache, in the traced window (from the first request
sent to the last returned), read from the program's compile counter
(``repro.core.trace.compiles``, a ``jax.monitoring`` listener).  Nothing
should compile in a window: 0.  A program without that counter reads
nothing."""


def read(run):
    try:
        from repro.core.trace import compiles
    except ImportError:
        return None
    if run.trace is None or not run.records:
        return None
    return compiles.backend_between(run.records[0][1], run.records[-1][2])
