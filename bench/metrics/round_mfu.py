"""Model FLOP utilisation of the round, in %: the model operations of
the window's trained rows (3x the forward pass per sample, see
``bench/counts.py``) over the window's seconds, over the chip's bf16
peak. Read in the traced run, from its untraced window."""


def read(run):
    if not run.get("peaks"):
        return None
    peak = run["peaks"]["bf16_flops_per_s"]
    return 100.0 * run["model_flops"] / run["window_s"] / peak
