"""Model FLOPs of the scoring tasks counted in the window over the window
times the chip's bf16 peak, in percent."""
from harness.readers import mfu_percent


def read(run):
    return mfu_percent(run, "score")
