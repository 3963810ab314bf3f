"""Bytes and operations of the ``minicpm_sala`` configuration's three
mechanisms, from shapes and the program's counters alone (``sizes`` is the
configuration file's dict, or the runner's ``record["sala"]`` cut of it).
Nothing here imports the program.

- The sparse attend reads, per block WALKED (the counter
  ``sparse_blocks_read``: one a stream, sparse layer, K/V head and chosen
  block), one head's K tile and V tile: ``2 x block x head_dim`` cache
  entries; it spends ``4 x group x head_dim`` operations a key row.
- The selection scores, per pooled row SCORED (``ck_rows_scored``: one a
  stream, sparse layer, K/V head and visible pooled key), ``head_dim`` cache
  entries against ``group`` query heads: ``2 x group x head_dim`` operations.
- The Lightning state update reads and writes every live stream's state of
  every Lightning layer once: ``lightning_nh x d x d`` float32 entries, 5
  operations an entry (decay, outer product, read).
"""


def _sparse(sizes):
    return (sizes.get("assumed") or {}).get("sparse_config") or {}


def group(sizes) -> int:
    return int(sizes["num_attention_heads"]) \
        // int(sizes["num_key_value_heads"])


def lightning_layers(sizes) -> int:
    return sum(t == "lightning-attn" for t in sizes["mixer_types"])


def sparse_layers(sizes) -> int:
    return sum(t == "minicpm4" for t in sizes["mixer_types"])


def attend_block_bytes(sizes, itemsize: int = 2) -> int:
    """One K/V head's K and V tiles of one block."""
    return 2 * int(_sparse(sizes).get("block_size", 64)) \
        * int(sizes["head_dim"]) * itemsize


def attend_bytes(sizes, blocks_read: float) -> float:
    return float(blocks_read) * attend_block_bytes(sizes)


def attend_flops(sizes, blocks_read: float) -> float:
    return float(blocks_read) * int(_sparse(sizes).get("block_size", 64)) \
        * 4 * group(sizes) * int(sizes["head_dim"])


def select_bytes(sizes, rows_scored: float, itemsize: int = 2) -> float:
    return float(rows_scored) * int(sizes["head_dim"]) * itemsize


def select_flops(sizes, rows_scored: float) -> float:
    return float(rows_scored) * 2 * group(sizes) * int(sizes["head_dim"])


def state_entries(sizes) -> int:
    d = int(sizes["lightning_head_dim"])
    return int(sizes["lightning_nh"]) * d * d


def state_update_bytes(sizes, live_streams: float) -> float:
    return 2.0 * live_streams * lightning_layers(sizes) * 4 \
        * state_entries(sizes)


def state_update_flops(sizes, live_streams: float) -> float:
    return 5.0 * live_streams * lightning_layers(sizes) * state_entries(sizes)


def roofline_share(flops: float, bytes_: float, seconds: float,
                   peaks: dict) -> float:
    """The least time the chip could take over the kernel's time, in
    percent."""
    floor = max(flops / peaks["bf16_flops_per_s"],
                bytes_ / peaks["hbm_bytes_per_s"])
    return 100.0 * floor / seconds
