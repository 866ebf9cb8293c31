"""Share of its roofline, in %, that the int8 cold codec
(``kernels/cold_codec.py``: per-leaf absmax, quantise, dequantise)
reaches.

Roofline time of a round: the algorithmic bytes of decoding the paged
rows in and encoding them out (float32 rows to int8 codes and a scale
per leaf, and back; ``bench/counts.py``) over the HBM bandwidth. The
codec's time is the summed device time, per traced round, of the
Pallas calls that read or write int8 codes, and of the absmax calls,
which reduce a (rows, w) float32 block to (rows, 1).
"""
import re

ABSMAX = re.compile(r"= f32\[(\d+),1\]\S* custom-call\(f32\[(\d+),\d+\]")
PALLAS = "custom_call_target=\"tpu_custom_call\""


def is_codec(name):
    if PALLAS not in name or " custom-call(" not in name:
        return False
    if "s8[" in name:
        return True
    m = ABSMAX.search(name)
    return bool(m) and m.group(1) == m.group(2)


def read(run):
    t = run["trace"]
    ns = sum(e - s for name, s, e in t["device"] if is_codec(name))
    if not ns or not run.get("peaks") or "codec_bytes" not in t["counts"]:
        return None
    floor = t["counts"]["codec_bytes"] / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor / (ns * 1e-9 / t["rounds"])
