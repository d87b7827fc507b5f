"""halo_share.p2p: the rows of B that the chunked engine's cross-chip
exchange sends, as a percentage of the rows whole chunks would send,
summed over the kept block diagonals of the neighbour sums the process
built (``repro.core.trace.exchange``, counted when the program is traced).
A 4-regular lattice of 1,024 nodes on four chips sends 2 of 256 rows per
offset: 0.78%.  A program without that counter reads nothing."""


def read(run):
    try:
        from repro.core.trace import exchange
    except ImportError:
        return None
    return exchange.share()
