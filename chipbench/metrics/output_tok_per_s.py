"""Output tokens that landed on the host inside the window, per second of window."""
from chipbench.reduce import tokens_in_window


def read(run):
    return tokens_in_window(run) / run.seconds
