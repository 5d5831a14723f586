"""95th percentile of the gaps between consecutive output tokens of one
request, over every gap in the window (host clock, tokens as they land)."""
from chipbench.reduce import percentile, token_gaps


def read(run):
    gaps = token_gaps(run)
    return 1e3 * percentile(gaps, 95) if gaps else None
