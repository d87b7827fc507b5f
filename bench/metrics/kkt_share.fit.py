"""kkt_share.fit: the share of the traced window's leaf device time spent
in the program's ``decsvm.kkt_check`` scope (the stop rule's gradient at
the network mean, every ``check_every`` rounds), in percent of all leaf
time in the window, scoped or not (``bench/scopes.py``).  Nothing where the
trace holds no such scope."""

SCOPE = "decsvm.kkt_check"


def read(run):
    prog = run.program
    if prog is None or prog.scope_seconds.get(SCOPE, 0.0) <= 0.0:
        return None
    return 100.0 * prog.share(SCOPE)
