"""exchange_share.p2p: the share of the traced window's leaf device time
spent in the program's ``decsvm.exchange`` scope (the neighbour exchange
across chips: the ``ppermute`` of block diagonals and their off-diagonal
dots), in percent of all leaf time in the window, scoped or not
(``bench/scopes.py``), a mean over the cell's chips.  Nothing where the
trace holds no such scope."""

SCOPE = "decsvm.exchange"


def read(run):
    prog = run.program
    if prog is None or prog.scope_seconds.get(SCOPE, 0.0) <= 0.0:
        return None
    return 100.0 * prog.share(SCOPE)
