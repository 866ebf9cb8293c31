"""Host milliseconds of paging per round: the change of the program's
own counter of paging seconds (staging, fetch, commit, drain) over the
window, over its rounds."""


def read(run):
    return 1e3 * run["page_s"] / run["rounds"]
