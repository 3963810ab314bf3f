"""Bytes and operations the Kimi-Delta-Attention configuration's decode
step needs, from shapes and counts alone (``sizes`` is the runner's
``record["kda"]``: ``num_heads``, ``head_dim`` of ``linear_attn_config`` and
``layers_run``, the KDA layers the configuration RUNS).  Nothing here
imports the program; the counts read the work, not the implementation.

The state of one stream and layer is ``num_heads`` heads of ``head_dim`` x
``head_dim`` float32 entries.  A decode step reads and writes every LIVE
stream's state once and spends, per state entry, a multiply for the decay, a
multiply-add for ``k^T S'``, a multiply-add for the correction and a
multiply-add for the read ``S^T q``: 7 operations.  (The chunked delta rule
of prefill is plain ``jax.numpy``: it gets a count when it gets a kernel.)
"""


def state_entries(sizes: dict) -> int:
    """Entries of one stream's state of ONE layer."""
    return int(sizes["num_heads"]) * int(sizes["head_dim"]) ** 2


def state_bytes(sizes: dict) -> int:
    """One stream's state of ONE layer, float32."""
    return 4 * state_entries(sizes)


def state_update_bytes(sizes: dict, live_streams: float) -> float:
    """State bytes a decode execution moves over the KDA layers: every live
    stream's state read once and written once."""
    return 2.0 * live_streams * int(sizes["layers_run"]) * state_bytes(sizes)


def state_update_flops(sizes: dict, live_streams: float) -> float:
    """Decay, ``k^T S'``, the correction and the read: 7 operations a state
    entry."""
    return 7.0 * live_streams * int(sizes["layers_run"]) \
        * state_entries(sizes)


def roofline_share(flops: float, bytes_: float, seconds: float,
                   peaks: dict) -> float:
    """The least time the chip could take (the larger of operations over
    peak FLOP/s and bytes over peak bytes/s) over the kernel's time, in
    percent."""
    floor = max(flops / peaks["bf16_flops_per_s"],
                bytes_ / peaks["hbm_bytes_per_s"])
    return 100.0 * floor / seconds
