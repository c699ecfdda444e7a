"""Device self time of miss execution per gR step, in ms: the
``hop<h>/miss_exec`` scopes (the hop kernel's ``lax.cond`` and its
branches) of ``jit_fused`` in the profiler trace
(``bench/trace_scopes.py``), over the gR steps the loop ran."""

from bench import trace_scopes


def read(run):
    return trace_scopes.device_ms(run, "jit_fused", r"hop\d+/miss_exec",
                                  run.out.gr_steps)
