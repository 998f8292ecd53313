"""Host milliseconds of the scheduler tick itself: the median, over the
program's ``fs.tick`` spans in the traced window, of each tick's time
less the step launches (``fs.step``) and rebinds (``fs.rebind``) it
holds: sweeps, admission, the policy and the scheduler's bookkeeping."""
from harness.spans import median_self_ms, of_run


def read(rec):
    return median_self_ms(of_run(rec), "fs.tick", ("fs.step", "fs.rebind"))
