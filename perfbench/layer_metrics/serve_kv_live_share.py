"""KV cache: blocks of the pool that hold live contexts (the allocator's
``blocks_in_use()``, read once a second, mean over the window's second
half), as a share of ``num_blocks``, in percent.  A pool the traffic does
not fill is memory, and in this engine time, spent on nothing."""


def read(record):
    kv = record.get("kv") or {}
    if kv.get("live_blocks_mean") is None:
        return None
    return 100.0 * kv["live_blocks_mean"] / kv["num_blocks"]
