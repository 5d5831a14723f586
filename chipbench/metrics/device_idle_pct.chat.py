"""1 - device busy time inside the harness's engine.step spans over the time
in those spans, in the traced window (sleeps waiting for arrivals excluded)."""
from chipbench.reduce import idle_pct


def read(run):
    return idle_pct(run)
