"""JIT: JAX tracings inside the traced window, counted from the
``jit.trace`` instants the program's compile counter places on the
timeline (``repro.obs.trace.COMPILES``); none once every shape is warm."""
from bench import spans


def read(ctx):
    return spans.jit_traces_in_window(ctx)
