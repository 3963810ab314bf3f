"""Kernels: the grouped gated-SiLU product's share of its roofline in
decode where EVERY expert is held (``moe_expert_gemm_roofline``'s twin: that
one reads the latent family's sizes): the larger of (three H x F matrices
of every expert that got a row + the routed rows in and out) / peak bytes/s
and (6 H F FLOPs a routed pair) / peak FLOP/s (``lib/afmoe_costs.py``),
over ``_gswiglu_kernel``'s device time inside ``decode_step``.  Pairs and
empty experts are the ``decode`` spans' ``moe_held_pairs`` /
``moe_held_empty``.  Bound by bandwidth: about eight rows an expert."""
from perfbench.lib import afmoe_costs, scope_trace


def read(record):
    sizes = record.get("afmoe")
    secs, execs = scope_trace.kernel_seconds(record, "_gswiglu_kernel")
    pairs, n = scope_trace.span_arg_sum(record, "decode", "moe_held_pairs")
    empty, _ = scope_trace.span_arg_sum(record, "decode", "moe_held_empty")
    if not sizes or not secs or not execs or not n or not record.get("peaks"):
        return None
    layers = int(sizes["num_hidden_layers"]) - int(sizes["num_dense_layers"])
    with_rows = layers * int(sizes["num_experts"]) - (empty or 0.0) / n
    return afmoe_costs.roofline_share(
        afmoe_costs.expert_gemm_flops(sizes, pairs / n),
        afmoe_costs.expert_gemm_bytes(sizes, with_rows, pairs / n),
        secs / execs, record["peaks"])
