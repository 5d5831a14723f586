"""Expert GEMMs' share of their roofline at decode, in the traced window:
least time of the expert work of the traced steps that ran decodes and no
chunk (``works/moe_gqa.expert_work`` of each decode's real lanes: operations
of the assignments on the held experts, bytes of the held experts with a
token) over the device time of the ``pallas:gmm_*`` kernels that start inside
those steps' spans.

A step with a chunk is left out: its kernels cannot be told apart from its
decode's by name, and a chunk's tokens share one context, so on random
weights they route to a few experts and the uniform expectation counts more
experts read than the kernels read.  A decode's lanes are distinct requests,
whose routing the expectation describes."""
from chipbench.reduce import least_time


def read(run):
    steps = [s for s in run.traced_steps() if s.decodes and not s.chunks]
    if not steps:
        return None
    spans = {sp.step_id: sp for sp in run.trace.steps()}
    windows = sorted((spans[s.step_id].start, spans[s.step_id].end) for s in steps)
    dev, j = 0.0, 0
    for o in run.trace.ops:  # sorted by start, as are the windows
        if o.container or not o.name.startswith("pallas:gmm_"):
            continue
        while j < len(windows) and windows[j][1] <= o.start:
            j += 1
        if j < len(windows) and windows[j][0] <= o.start:
            dev += o.end - o.start
    if dev <= 0:
        return None
    least = sum(least_time(*run.work.expert_work(run.shapes, len(ctx)), run.peaks)
                for s in steps for _, _, ctx in s.decodes if ctx)
    return 100.0 * least / (dev / 1e9)
