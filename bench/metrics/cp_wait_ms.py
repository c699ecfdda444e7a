"""Mean wait of a miss in the program's CP queue, in ms: the program's
``cp_wait`` samples (``CachePopulator.drain``, from the miss's push
into its ``MissQueue`` to the commit of its entry) over the window's
committed inserts."""


def read(run):
    s = run.spans.get("cp_wait")
    return 1e3 * s["total_s"] / s["count"] if s and s["count"] else None
