"""Device time of the jitted gR serving steps, per step, in ms: the
``XLA Modules`` events of the hop driver's program (``jit_fused``) in the
profiler trace over the number of gR steps the loop ran."""

PROGRAM = "jit_fused"


def read(run):
    if run.trace is None or not run.out.gr_steps:
        return None
    mods = [v for k, v in run.trace["modules"].items() if k.startswith(PROGRAM)]
    if not mods:
        return None
    return 1e3 * sum(s for _, s in mods) / run.out.gr_steps
