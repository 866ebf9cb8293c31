"""Seconds per global round: the window, which closes when the last
round's device work has finished, over the rounds in it."""


def read(run):
    return run["window_s"] / run["rounds"]
