"""Host milliseconds per traced round of round assembly: the program's
``repro.assemble`` spans (``core/cefedavg.py``: engine draw, schedule,
working set, operators, ``_resolve_args``, forwarding indices), read
inside the running window, where dispatch may wait on the device's
queue in a span of its own. None where the program has no such span."""
from bench import scopes


def read(run):
    return scopes.span_ms(run, "assemble")
