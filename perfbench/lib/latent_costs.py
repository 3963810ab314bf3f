"""Operations and bytes the latent-attention configuration's two kernels
need, from shapes and counts alone (``sizes`` is the configuration file's
dict).  Nothing here imports the program.

- The latent attend reads, per live position and layer, ONE cache row
  ``[ckv | k_rope]`` (kv_lora_rank + qk_rope_head_dim values) whatever the
  head count, and spends per position, layer and query head a score
  product of that width and a value product of kv_lora_rank.
- The held experts' grouped product reads the three matrices of every held
  expert that got at least one row (an expert nobody chose moves nothing),
  reads and writes each routed row once, and spends three H x F products a
  routed (token, expert) pair.
"""


def _itemsize(sizes: dict) -> int:
    return 2                      # bf16 weights and cache (assumed.dtype)


def latent_row_bytes(sizes: dict) -> int:
    return (int(sizes["kv_lora_rank"]) + int(sizes["qk_rope_head_dim"])) \
        * _itemsize(sizes)


def latent_attend_bytes(sizes: dict, live_positions: int) -> int:
    """Cache bytes the attends of every layer stream for
    ``live_positions`` cached positions (summed over streams)."""
    return int(live_positions) * latent_row_bytes(sizes) \
        * int(sizes["num_hidden_layers"])


def latent_attend_flops(sizes: dict, live_positions: int,
                        rows_per_stream: int = 1) -> int:
    """Scores (width kv_lora_rank + qk_rope_head_dim) and values (width
    kv_lora_rank), 2 FLOPs a multiply-add, for every query head."""
    width = 2 * int(sizes["kv_lora_rank"]) + int(sizes["qk_rope_head_dim"])
    return (int(live_positions) * int(rows_per_stream)
            * int(sizes["num_attention_heads"]) * width * 2
            * int(sizes["num_hidden_layers"]))


def expert_gemm_bytes(sizes: dict, experts_with_rows: int,
                      pairs: int) -> int:
    H, F = int(sizes["hidden_size"]), int(sizes["moe_intermediate_size"])
    b = _itemsize(sizes)
    return int(experts_with_rows) * 3 * H * F * b + int(pairs) * 2 * H * b


def expert_gemm_flops(sizes: dict, pairs: int) -> int:
    H, F = int(sizes["hidden_size"]), int(sizes["moe_intermediate_size"])
    return int(pairs) * 6 * H * F


def roofline_share(flops: float, bytes_: float, seconds: float,
                   peaks: dict) -> float:
    """The least time the chip could take (the larger of operations over
    peak FLOP/s and bytes over peak bytes/s) over the kernel's time, in
    percent."""
    floor = max(flops / peaks["bf16_flops_per_s"],
                bytes_ / peaks["hbm_bytes_per_s"])
    return 100.0 * floor / seconds
