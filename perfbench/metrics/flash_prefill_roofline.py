"""The paged flash-prefill kernel's share of its roofline in the traced
window: least time for its calls (causal FLOPs over peak, or the prompt
context's K/V pages read once plus queries and outputs over HBM
bandwidth, whichever is larger) over the traced kernel time, in %."""
from harness.device import kernel_roofline


def read(rec):
    return kernel_roofline(rec, "flash_prefill")
