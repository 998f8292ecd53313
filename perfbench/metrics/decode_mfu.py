"""Model FLOPs of the decode rows launched in the traced window over
the device time of the decode and mixed steps x the chip's bf16 peak, in
% (the whole step's share of the peak, which bounds any kernel's gain)."""
from harness.device import mfu


def read(rec):
    return mfu(rec, "decode")
