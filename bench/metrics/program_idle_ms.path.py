"""program_idle_ms.path: device idle time under the program's own host
spans of a tuned path (``decsvm:lambda_grid``, ``decsvm:path_program``,
``decsvm:bic_table``), cut at the spans' edges (``bench/scopes.py``), in
milliseconds per path completed in the traced window.  Nothing where no
idle time falls under these spans."""

from bench import scopes


def read(run):
    prog = run.program
    if prog is None or not run.done:
        return None
    idle = [prog.span_idle_seconds[k] for k in scopes.SPANS
            if k in prog.span_idle_seconds]
    return 1e3 * sum(idle) / len(run.done) if idle else None
