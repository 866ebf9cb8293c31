"""Host milliseconds inside ``step_round`` per round: scenario draw,
operator build, argument assembly and dispatch. Read in the traced run
from the benchmark's own span around a few calls, each made once the
previous round has finished: in the window the calls run ahead of the
device and, once its queue is full, wait inside ``step_round``."""


def read(run):
    probe = run.get("host_probe_s")
    if not probe:
        return None
    return 1e3 * sum(probe) / len(probe)
