"""Model FLOPs of every task of the window's iterations (training counted as
three forward passes) over the window times the chip's bf16 peak, in
percent."""
from harness.readers import mfu_percent


def read(run):
    return mfu_percent(run) if run.iterations else None
