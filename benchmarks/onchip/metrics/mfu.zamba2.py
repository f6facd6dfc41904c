"""Model FLOPs of the generation tasks counted in the window (prefill and
decode, ``harness/flops_zamba2.py``) over the window times the chip's bf16
peak, in percent."""
from harness.readers import mfu_percent


def read(run):
    return mfu_percent(run, "generate_loop")
