"""Seconds from the process's start to the first timed round: imports,
data, weights, compilation, the checked first rounds and the warm-up."""


def read(run):
    return run["setup_s"]
