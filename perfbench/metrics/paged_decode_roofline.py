"""The paged decode kernel's share of its roofline in the traced
window: the least time the chip could take for its calls (FLOPs over
peak or live K/V page bytes over HBM bandwidth, whichever is larger),
over the time the trace shows them taking, in %."""
from harness.device import kernel_roofline


def read(rec):
    return kernel_roofline(rec, "paged_decode")
