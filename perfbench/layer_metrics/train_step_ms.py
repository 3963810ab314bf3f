"""Training engine: median host-clock time from one optimizer step's end
(its loss fetched, ``block_until_ready``) to the next one's, over the
traced run's window; the steps are queued ``steps_in_flight`` deep, so
this is the device's step and not the host's round trip."""
import statistics


def read(record):
    if not record.get("step_s"):
        return None
    return statistics.median(record["step_s"]) * 1e3
