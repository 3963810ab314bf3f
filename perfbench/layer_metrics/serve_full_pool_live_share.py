"""KV cache: blocks of the ``full`` class's pool that hold live contexts
(that class's allocator's ``blocks_in_use()``, read once a second, mean
over the window's second half), as a share of the class's blocks, in
percent.  ``None`` for a model whose cache has no such class."""


def read(record):
    c = ((record.get("kv") or {}).get("classes") or {}).get("full") or {}
    if c.get("live_blocks_mean") is None:
        return None
    return 100.0 * c["live_blocks_mean"] / c["num_blocks"]
