"""Training engine: ``memory_stats()["peak_bytes_in_use"]`` of the fullest
device after the window, in GB (1e9 bytes).  It caps the micro-batch."""


def read(record):
    if record.get("kind") != "train" or not record.get("memory_peak_bytes"):
        return None
    return record["memory_peak_bytes"] / 1e9
