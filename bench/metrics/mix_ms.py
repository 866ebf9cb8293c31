"""Device milliseconds of mixing per traced round: the union of the
intervals of the operations under ``jax.named_scope("mix")``
(``kernels/gossip_mix.py`` ``gossip_mix_rows``: the Pallas kernel and
its pad and slice copies). None where no operation carries the scope."""
from bench import scopes


def read(run):
    return scopes.scope_ms(run, scopes.SCOPES["mix"])
