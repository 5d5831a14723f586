"""Process start to the window's opening: imports, tune, weights, engine,
warm-up of the cell's shapes and the lead-in traffic."""


def read(run):
    return run.setup_s
