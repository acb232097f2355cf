"""Process start to the window's opening: loading, weights, compiling
(or reading the compile cache) and warm-up."""


def read(run):
    return run.setup_s
