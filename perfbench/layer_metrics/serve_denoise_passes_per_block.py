"""Serving engine, a model generated by diffusion over blocks: the passes a
block costs, its commit included — slot-passes over the window's decode
iterations (``snapshot()["model_counters"]``: the mean ``block_rows`` an
iteration computed for live streams, over the block's length) per block
committed (the mean ``commits``).  The procedure's own number, not its
speed: 3.0 at two denoising steps under the static rule; a change that
moves it changed what is computed.  ``None`` for a model of tokens."""


def read(record):
    c = (record.get("snapshot") or {}).get("model_counters") or {}
    size = (record.get("blockgen") or {}).get("block_length")
    if not size or not c.get("commits") or "block_rows" not in c:
        return None
    return c["block_rows"] / size / c["commits"]
