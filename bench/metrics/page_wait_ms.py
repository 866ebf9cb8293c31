"""Host milliseconds per traced round that ``step_round`` blocks on
the previous round's results before committing them to the store: the
program's ``repro.page.wait`` spans (``core/cefedavg.py``, the drain's
wait for the device and for the D2H copy). None where the program has
no such span."""
from bench import scopes


def read(run):
    return scopes.span_ms(run, "page.wait")
