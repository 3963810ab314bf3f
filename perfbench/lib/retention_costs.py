"""Bytes and operations the power-retention configuration's decode kernel
needs, from shapes and counts alone (``sizes`` is the configuration
file's dict, or the runner's ``record["retention"]`` cut of it).  Nothing
here imports the program.

The state of one stream, layer and K/V head is the ``D (D + 1) / 2``
DISTINCT pairwise products of a key's ``D`` values (8,256 at D = 128),
each with ``D`` value columns and one normaliser entry, in float32 —
however the program holds them (it holds 8,320 rows so that every row is a
full lane row; the 64 doubled rows are its overhead, not work).  A decode
step reads and writes every LIVE stream's state once and spends, per
stream and layer, a multiply-add per state entry for the update and one
for each query head's read.
"""


def feature_width(sizes: dict) -> int:
    d = int(sizes["head_dim"])
    return d * (d + 1) // 2


def state_bytes(sizes: dict) -> int:
    """One stream's state of ONE layer: S and its normaliser, float32."""
    return (int(sizes["num_key_value_heads"]) * feature_width(sizes)
            * (int(sizes["head_dim"]) + 1) * 4)


def state_update_bytes(sizes: dict, live_streams: float) -> float:
    """State bytes a decode execution moves over all layers: every live
    stream's state read once and written once."""
    return 2.0 * live_streams * int(sizes["num_hidden_layers"]) \
        * state_bytes(sizes)


def state_update_flops(sizes: dict, live_streams: float) -> float:
    """The update (one multiply-add a state entry) and every query head's
    read of its K/V head's state (one a state entry and head)."""
    per_head = feature_width(sizes) * (int(sizes["head_dim"]) + 1) * 2
    return live_streams * int(sizes["num_hidden_layers"]) * per_head * (
        int(sizes["num_key_value_heads"]) + int(sizes["num_attention_heads"]))


def roofline_share(flops: float, bytes_: float, seconds: float,
                   peaks: dict) -> float:
    """The least time the chip could take (the larger of operations over
    peak FLOP/s and bytes over peak bytes/s) over the kernel's time, in
    percent."""
    floor = max(flops / peaks["bf16_flops_per_s"],
                bytes_ / peaks["hbm_bytes_per_s"])
    return 100.0 * floor / seconds
