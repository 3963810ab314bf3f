"""Bytes and operations the state-space configuration's two forms need,
from shapes and counts alone (``sizes`` is the configuration file's dict, or
the runner's ``record["ssm"]`` cut of it).  Nothing here imports the
program.

The state of one stream and layer is ``mamba_n_heads`` heads of
``mamba_d_state`` x ``mamba_d_head`` float32 entries.  A decode step reads
and writes every LIVE stream's state once and spends, per state entry, a
multiply for the decay, a multiply-add for the new outer product and a
multiply-add for the read: 5 operations.  The chunked scan over ``rows`` of
one stream in sub-chunks of ``chunk`` spends, per row and head, the
``C . B`` scores of its sub-chunk (shared by a group's heads), their
products with the rows' ``x``, the read of the carried state and the row's
own outer product; it is counted in multiply-adds of the published widths,
whatever passes the program makes of an fp32 product.
"""


def state_entries(sizes: dict) -> int:
    """Entries of one stream's state of ONE layer."""
    return (int(sizes["mamba_n_heads"]) * int(sizes["mamba_d_state"])
            * int(sizes["mamba_d_head"]))


def state_bytes(sizes: dict) -> int:
    """One stream's state of ONE layer, float32."""
    return 4 * state_entries(sizes)


def state_update_bytes(sizes: dict, live_streams: float) -> float:
    """State bytes a decode execution moves over all layers: every live
    stream's state read once and written once."""
    return 2.0 * live_streams * int(sizes["num_hidden_layers"]) \
        * state_bytes(sizes)


def state_update_flops(sizes: dict, live_streams: float) -> float:
    """Decay, outer product and read: 5 operations a state entry."""
    return 5.0 * live_streams * int(sizes["num_hidden_layers"]) \
        * state_entries(sizes)


def chunk_scan_flops(sizes: dict, rows: float, chunk: int) -> float:
    """The chunked scan over ``rows`` rows of a stream, all layers, 2
    operations a multiply-add."""
    nh, N, P = (int(sizes["mamba_n_heads"]), int(sizes["mamba_d_state"]),
                int(sizes["mamba_d_head"]))
    G = int(sizes["mamba_n_groups"])
    per_row = (2 * G * chunk * N          # C . B against the sub-chunk
               + 2 * nh * chunk * P       # the scores' products with x
               + 2 * nh * N * P           # the carried state's read
               + 2 * nh * N * P)          # the row's outer product
    return float(rows) * int(sizes["num_hidden_layers"]) * per_row


def roofline_share(flops: float, bytes_: float, seconds: float,
                   peaks: dict) -> float:
    """The least time the chip could take (the larger of operations over
    peak FLOP/s and bytes over peak bytes/s) over the kernel's time, in
    percent."""
    floor = max(flops / peaks["bf16_flops_per_s"],
                bytes_ / peaks["hbm_bytes_per_s"])
    return 100.0 * floor / seconds
