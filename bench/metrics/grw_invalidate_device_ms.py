"""Device self time of the gRW commit's phase B per commit, in ms: the
``grw/invalidate`` scope (the routed op stream applied to the cache) of
``jit_local_grw`` in the profiler trace (``bench/trace_scopes.py``),
over the commits the loop ran."""

from bench import trace_scopes


def read(run):
    return trace_scopes.device_ms(run, "jit_local_grw", r"grw/invalidate",
                                  run.out.commit_steps)
