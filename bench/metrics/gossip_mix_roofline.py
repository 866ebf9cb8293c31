"""Share of its roofline, in %, that the mixing kernel
(``kernels/gossip_mix.py`` ``gossip_mix_rows``) reaches.

Roofline time of a round: each mixing pass reads and writes the
(rows, T) float32 state once and reads the (rows, rows) operator, so it
takes at least max(bytes / HBM bandwidth, flops / bf16 peak); a round
makes ``mix_passes`` of them (``bench/counts.py``, from the
configuration). The kernel's time is the summed device time of the
Pallas calls of the shape W(a, b) x Y(b, T) -> (a, T) in the traced
window, per traced round. The pad and slice copies around the kernel
are separate operations and count outside it. At these shapes bytes
bound the pass.
"""
import re

#: a Pallas call computing (a, T) from W (a, b) and Y (b, T)
MIX = re.compile(r"= f32\[(\d+),(\d+)\]\S* custom-call\(f32\[(\d+),(\d+)\]"
                 r"\S* %\S+, f32\[(\d+),(\d+)\]\S* %\S+\),"
                 r" custom_call_target=\"tpu_custom_call\"")


def is_mix(name):
    m = MIX.search(name)
    if not m:
        return False
    a, t, wa, wb, yb, yt = map(int, m.groups())
    return a == wa and wb == yb and t == yt


def read(run):
    t = run["trace"]
    ns = sum(e - s for name, s, e in t["device"] if is_mix(name))
    if not ns or not run.get("peaks"):
        return None
    c, pk = t["counts"], run["peaks"]
    floor = max(c["mix_bytes"] / pk["hbm_bytes_per_s"],
                c["mix_flops"] / pk["bf16_flops_per_s"])
    return 100.0 * c["mix_passes"] * floor / (ns * 1e-9 / t["rounds"])
