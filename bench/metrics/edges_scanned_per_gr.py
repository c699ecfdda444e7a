"""Adjacency rows the miss executor scanned per gR-Tx over the window:
the steps' ``edges_scanned`` (the owner-stage counter) over the gR-Txs
served."""


def read(run):
    n = run.out.gr_requests
    return run.out.gr_sums.get("edges_scanned", 0) / n if n else None
