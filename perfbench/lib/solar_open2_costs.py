"""What the Solar-Open2 configuration's programs must move and what its
counters mean, from shapes and counts alone (nothing here imports the
program).  The kernels this configuration runs are the repository's
(``_pattn_kernel``, ``_kda_state_update_kernel``, ``_gswiglu_kernel``) and
their operations and bytes are counted where their rooflines' readers
already look: ``lib/afmoe_costs.py`` (the grouped-head attend: one K and one
V row of every K/V head a key row IN REACH, whatever the kernel walks),
``lib/kda_costs.py`` (one read and one write of every live page's layer) and
``lib/latent_costs.py`` (the held experts' product).  ``record_sizes`` is the
one place that says which numbers of THIS configuration those functions
read; the rest are the sizes this cell is reckoned by.
"""

_ITEMSIZE = 2                     # bf16 weights, K/V rows and filter rows


def record_sizes(sizes: dict, layers: dict) -> dict:
    """The runner's record keys the accepted cost functions read, from the
    configuration file's dict and the program's layer counts (``kda``,
    ``gqa``, ``moe`` layers run, ``experts_held``)."""
    lin = sizes["linear_attn_config"]
    return {
        "kda": {"num_heads": int(lin["num_heads"]),
                "head_dim": int(lin["head_dim"]),
                "layers_run": int(layers["kda"]),
                "moe_layers": int(layers["moe"]),
                "experts_held": int(layers["experts_held"])},
        # (``afmoe_costs.attend_*``: the decode span's
        # ``context_tokens_in_reach`` already sums over the K/V layers)
        "afmoe": {k: int(sizes[k]) for k in (
            "hidden_size", "moe_intermediate_size", "num_attention_heads",
            "num_key_value_heads", "head_dim")},
        # (``latent_costs.expert_gemm_*`` read these two alone)
        "latent": {k: int(sizes[k]) for k in (
            "hidden_size", "moe_intermediate_size")}}


def kv_token_bytes(sizes: dict, gqa_layers: int) -> int:
    """K and V rows a token keeps over the grouped-query layers run."""
    return 2 * int(sizes["num_key_value_heads"]) * int(sizes["head_dim"]) \
        * _ITEMSIZE * int(gqa_layers)


def state_page_bytes(sizes: dict, kda_layers: int) -> int:
    """A stream's page over the KDA layers run: the float32 state and the
    filters' held rows."""
    lin = sizes["linear_attn_config"]
    nh, d = int(lin["num_heads"]), int(lin["head_dim"])
    taps = int(lin["short_conv_kernel_size"])
    return int(kda_layers) * (4 * nh * d * d
                              + (taps - 1) * 3 * nh * d * _ITEMSIZE)


def snapshot_worth_tokens(sizes: dict, kda_layers: int, gqa_layers: int
                          ) -> float:
    """Tokens of K/V a snapshot's bytes would keep: what a page in the
    prefix cache is weighed against."""
    return state_page_bytes(sizes, kda_layers) \
        / kv_token_bytes(sizes, gqa_layers)
