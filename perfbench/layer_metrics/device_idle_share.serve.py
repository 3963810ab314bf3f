"""Device: 1 - (union of device-operation intervals) / (traced window)
over a traced stretch of decode iterations in mid-window."""


def read(record):
    tr = record.get("trace")
    if not tr or record.get("kind") != "serve":
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
