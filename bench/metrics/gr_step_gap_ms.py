"""Device-idle time inside the benchmark's ``gr_step.<plan>``
annotations per gR step, in ms: the idle intervals of the profiler trace
that fall inside a gR step call, whichever program span the host was in
(``bench/trace_scopes.py`` splits them by span and logs the table)."""

from bench import trace_scopes


def read(run):
    red = trace_scopes.for_run(run)
    if red is None or not run.out.gr_steps:
        return None
    idle = sum(s for note, table in red["idle"].items()
               if note.startswith("gr_step.") for s in table.values())
    return 1e3 * idle / run.out.gr_steps
