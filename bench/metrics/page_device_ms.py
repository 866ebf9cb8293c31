"""Device milliseconds of paging per traced round: the union of the
intervals of the operations under ``jax.named_scope("page_in")`` and
``("page_out")`` (the pipelined streamed round's pre and post jits: reference
gather, forwarding scatter, the cold codec's decode and encode). The
host-device copies are no operations and are not in it. None where no
operation carries the scopes."""
from bench import scopes


def read(run):
    return scopes.scope_ms(run, scopes.SCOPES["page_device"])
