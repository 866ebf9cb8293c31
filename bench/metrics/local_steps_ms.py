"""Device milliseconds of the local steps per traced round: the union of
the intervals of the operations under ``jax.named_scope("local_steps")``
(``core/cefedavg.py`` ``_train_scan``: forward, backward and the
SGD-momentum update of each local step), loops left out. None where no
operation carries the scope."""
from bench import scopes


def read(run):
    return scopes.scope_ms(run, scopes.SCOPES["local_steps"])
