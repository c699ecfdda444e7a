"""The cache probe's hit share over the window, in %: ``hits`` over
``cache_reads`` (probes of cached templates) summed over the gR steps'
metrics."""


def read(run):
    reads = run.out.gr_sums.get("cache_reads", 0)
    return 100.0 * run.out.gr_sums.get("hits", 0) / reads if reads else None
