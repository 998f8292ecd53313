"""Host-device synchronizations per step launched in the window, from
the engine's ``sync_stats``: batched token harvests, in-flight window
waits, drains and per-token host reads, over steps."""


def read(rec):
    d = rec.sync_delta
    if not d.get("steps"):
        return None
    syncs = d["d2h_batched"] + d["window_waits"] + d["drains"] \
        + d["host_argmax"]
    return syncs / d["steps"]
