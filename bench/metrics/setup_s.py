"""Process start to the opening of the timed window: loading, weights,
compiling (or loading compiled programs) and the warm-up."""


def read(run):
    return run.setup_s
