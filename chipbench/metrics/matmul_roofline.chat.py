"""Matrix products' share of their roofline in the traced window: least time
(larger of operations over peak FLOP/s and bytes over peak bytes/s, from the
unpadded shapes the hooks reported) over the device time of every op that
computes them (Pallas kernels and XLA dot/convolution fusions alike)."""
from chipbench.reduce import roofline_pct


def read(run):
    return roofline_pct(run)
