"""Mean host-clock time of one CP drain call over the window, in ms: the
benchmark's ``cp_drain`` span around ``ShardedMissDrain.push`` and
``drain``, ending in ``block_until_ready`` on the cache."""


def read(run):
    n, s = run.out.spans.get("cp_drain", (0, 0.0))
    return 1e3 * s / n if n else None
