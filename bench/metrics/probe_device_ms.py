"""Device self time of the cache probe per gR step, in ms: the
``hop<h>/probe`` scopes of the hop driver's program (``jit_fused``) in
the profiler trace (``bench/trace_scopes.py``), over the gR steps the
loop ran."""

from bench import trace_scopes


def read(run):
    return trace_scopes.device_ms(run, "jit_fused", r"hop\d+/probe", run.out.gr_steps)
