"""Model FLOPs of the prompt chunks launched in the traced window
(attention over prior context included) over the device time of the
prefill and mixed steps x the chip's bf16 peak, in %."""
from harness.device import mfu


def read(rec):
    return mfu(rec, "prefill")
