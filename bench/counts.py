"""Operations and bytes of one round, from the configuration's shapes.

Nothing here is read from the compiled program: the numbers are what
the algorithm needs, so a change to the program cannot move them.
"""
from __future__ import annotations

import json
import os
from typing import Dict

#: bytes of one float32 and of one int8 cold-store scale
F32 = 4


def model_flops(cfg: Dict, model_mod, trained_rows: int) -> float:
    """Model operations of ``trained_rows`` row-rounds: 3x the forward
    pass (forward and backward) per sample, times the batch, times the
    local steps of a round. Mixing and the optimiser count nothing."""
    fed, opt = cfg["federation"], cfg["optimizer"]
    per_sample = model_mod.forward_flops(cfg["model_sizes"])
    steps = fed["tau"] * fed["q"]
    return 3.0 * per_sample * opt["batch"] * steps * trained_rows


def mix_passes(fed: Dict) -> int:
    """Mixing passes of one round of the canonical program, lowered with
    adjacent mixes fused: one per tau-boundary (q of them); the
    q*tau-boundary's backhaul gossip folds into the last one."""
    return int(fed["q"])


def mix_bytes(rows: int, total: int) -> int:
    """Bytes one mixing pass must move: the (rows, T) float32 state read
    and written once, and the (rows, rows) operator read."""
    return 2 * rows * total * F32 + rows * rows * F32


def mix_flops(rows: int, total: int) -> int:
    """Operations of one mixing pass: a (rows, rows) x (rows, T) product."""
    return 2 * rows * rows * total


def codec_bytes(rows: int, total: int, segments: int) -> int:
    """Bytes the int8 cold codec must move for ``rows`` paged rows:
    decode reads codes and scales and writes float32 rows; encode reads
    float32 rows and writes codes and scales."""
    encoded = rows * total + rows * segments * F32
    return 2 * (encoded + rows * total * F32)


def round_counts(cfg: Dict, eng) -> Dict:
    """This cell's per-round kernel work, for the roofline readers."""
    total = int(eng.layout.total)
    segs = len(eng.layout.segments)
    rows = eng.mix_rows()
    out = {"mix_passes": mix_passes(cfg["federation"]),
           "mix_bytes": mix_bytes(rows, total),
           "mix_flops": mix_flops(rows, total)}
    pop = cfg.get("population")
    if pop is not None and pop["codec"] == "int8":
        paged = eng.paged_rows()
        out["codec_bytes"] = codec_bytes(paged, total, segs)
    return out


def peaks(device_kind: str) -> Dict:
    """The chip's published peaks (``bench/peaks.json``); a chip not in
    the table is an error, never a default."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)["chips"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table[device_kind]
