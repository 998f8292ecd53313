"""Share of the traced window in which the device was idle while the
host was outside every scheduler tick (``fs.tick``): in the stream pump,
drains, HTTP writes or the event loop, in %, from the chips' mean busy
time. At most ``device_idle_share`` of the same run."""
from harness.spans import idle_outside_share, of_run


def read(rec):
    if rec.traced is None:
        return None
    return idle_outside_share(rec.traced.trace, of_run(rec), "fs.tick",
                              rec.traced.seconds)
