"""Device: 1 - (union of device-operation intervals) / (traced window),
mean over the cell's chips, over the traced optimizer steps."""


def read(record):
    tr = record.get("trace")
    if not tr or record.get("kind") != "train":
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
