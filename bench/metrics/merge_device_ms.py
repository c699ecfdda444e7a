"""Device self time of the frontier merge and the final clause per gR
step, in ms: the ``hop<h>/merge`` and ``finalize`` scopes of
``jit_fused`` in the profiler trace (``bench/trace_scopes.py``), over
the gR steps the loop ran."""

from bench import trace_scopes


def read(run):
    return trace_scopes.device_ms(run, "jit_fused", r"hop\d+/merge|finalize",
                                  run.out.gr_steps)
