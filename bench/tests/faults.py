"""Faults planted in the program underneath a benchmark run, for the
tests that see the check refuse them."""
import contextlib
import dataclasses

import jax.numpy as jnp
import numpy as np


@contextlib.contextmanager
def planted(monkeypatch, fault: str, per: int = 0):
    """``fault`` planted in the program; ``per`` is the devices of an
    edge server, which ``wrong_rows`` needs."""
    from repro.core import cefedavg, clientstore
    sim = cefedavg.FLSimulator
    if fault == "state_unchanged":
        # every lowered round hands back the state it was given
        get = sim._get_round

        def _get_round(self, kind, program):
            fn = get(self, kind, program)
            keep = 3 if kind == "flat" else 2
            return lambda *a, **k: tuple(a[:keep])
        monkeypatch.setattr(sim, "_get_round", _get_round)
    elif fault == "half_batch":
        loss = sim._loss

        def _loss(self, p, x, y):
            h = x.shape[0] // 2
            return loss(self, p, x[:h], y[:h])
        monkeypatch.setattr(sim, "_loss", _loss)
    elif fault == "no_mixing":
        monkeypatch.setattr(cefedavg, "gossip_mix_rows", lambda W, Y, **k: Y)
    elif fault == "wrong_rows":
        # the compacted round gathers one participant's row from, and
        # scatters it to, an idle device of its edge server
        from bench.readings import moved_row
        plan = cefedavg.compact_plan

        def compact_plan(mask, buckets=None):
            cp = plan(mask, buckets)
            pair = moved_row(np.asarray(mask), per)
            if pair is None:
                return cp
            i, j = pair
            idx = cp.idx.copy()
            at_i, at_j = np.nonzero(idx == i)[0], np.nonzero(idx == j)[0]
            idx[at_i] = j
            idx[at_j] = i       # j among the padding lanes: i takes its place
            return dataclasses.replace(cp, idx=idx)
        monkeypatch.setattr(cefedavg, "compact_plan", compact_plan)
    elif fault == "momentum_altered":
        # the page-out writes each momentum row back at twice its size
        commit = clientstore.ClientStore.commit_encoded

        def commit_encoded(self, clients, q, scale):
            return commit(self, clients, q, jnp.asarray(scale) * 2)
        monkeypatch.setattr(clientstore.ClientStore, "commit_encoded",
                            commit_encoded)
    else:
        raise ValueError(fault)
    yield
