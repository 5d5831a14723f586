"""Median host-clock period between consecutive decodes at the full lane
width (the engine's on_decode hook), in the window."""
from chipbench.reduce import decode_periods, median


def read(run):
    m = median(decode_periods(run))
    return None if m is None else 1e3 * m
