"""Device self time of the gRW commit's phase A per commit, in ms: the
``grw/apply`` (mutations) and ``grw/derive`` (the listener) scopes of
the commit's program (``jit_local_grw``) in the profiler trace
(``bench/trace_scopes.py``), over the commits the loop ran."""

from bench import trace_scopes


def read(run):
    return trace_scopes.device_ms(run, "jit_local_grw", r"grw/(apply|derive)",
                                  run.out.commit_steps)
