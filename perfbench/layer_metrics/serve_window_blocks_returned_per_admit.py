"""KV cache: ring blocks the ``window`` class gave back WHILE prompts were
admitted (the window sliding during prefill, chunk by chunk:
``snapshot()["prefill_window_blocks_returned"]``), per request that
started, over the whole run.  0 where no prompt outgrows the window;
``None`` for a program without the counter."""


def read(record):
    snap = record.get("snapshot") or {}
    started = (record.get("summary") or {}).get("started")
    if "prefill_window_blocks_returned" not in snap or not started:
        return None
    return snap["prefill_window_blocks_returned"] / started
