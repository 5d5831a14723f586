"""Forward operations of the tokens processed in the window (chunk tokens
computed plus tokens decoded; prefix-reused tokens not counted) over the
window's seconds times the chip's peak FLOP/s."""


def read(run):
    flops = sum(run.step_work(s)[0] for s in run.window_steps())
    return 100.0 * flops / (run.seconds * run.peaks["bf16_flops_per_s"])
