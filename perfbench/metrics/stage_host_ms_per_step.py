"""Host milliseconds of staging one step: the median, over the
program's ``fs.step`` spans in the traced window, of each step's time
less its window waits (``fs.wait``) and token harvests (``fs.harvest``):
batch building, uploads and the launch of the step program."""
from harness.spans import median_self_ms, of_run


def read(rec):
    return median_self_ms(of_run(rec), "fs.step", ("fs.wait", "fs.harvest"))
