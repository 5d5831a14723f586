"""The ``pallas:gmm_*`` kernels' share of the device's busy time in the
traced window (both over the range of the traced steps)."""
from chipbench import trace_reduce


def read(run):
    if run.trace is None or run.trace.range() is None:
        return None
    lo, hi = run.trace.range()
    busy, _ = trace_reduce.busy(run.trace)
    gmm = sum(o.end - o.start for o in run.trace.ops
              if o.name.startswith("pallas:gmm_") and lo <= o.start < hi) / 1e9
    if busy <= 0 or gmm <= 0:
        return None
    return 100.0 * gmm / (busy * run.trace.n_devices)
