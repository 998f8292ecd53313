"""Set-up time: from the process's start to the window's start (import,
weights, KV pool, stack, HTTP server, the warm-up replay of the cell's
traffic, and the lead-in that brings the server to a steady state), on
the host clock."""


def read(rec):
    return rec.setup_s
