"""Backend compiles (or compile-cache loads) JAX reported inside the window."""


def read(run):
    return sum(run.t_open <= t < run.t_close for t in run.compile_times)
