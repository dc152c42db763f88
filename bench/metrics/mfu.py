"""Model FLOPs of the window (``flops.py``: forward and backward of every
real client-step, and the evaluation forward) over the window's seconds
times the chips times the chip's bf16 peak, in per cent: the per-tier
cohort programs' share of what the chips could do."""


def read(rec):
    if rec["peak"] is None:
        return None
    return 100.0 * rec["model_flops"] / (
        rec["window_s"] * rec["chips"] * rec["peak"]["bf16_flops_per_s"])
