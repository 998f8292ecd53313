"""Share of the traced window in which no operation ran on the device,
averaged over the chips, in %."""
from harness.device import idle_share


def read(rec):
    return idle_share(rec)
