"""Kernels: the grouped gated-SiLU product's share of its roofline in a
block pass where EVERY expert is held and every layer is an expert layer
(``moe_all_experts_gemm_roofline``'s twin: that one reads
``record["afmoe"]`` and its dense layers): the larger of (three H x F
matrices of every expert that got a row + the routed rows in and out) /
peak bytes/s and (6 H F FLOPs a routed pair, F = 768) / peak FLOP/s
(``lib/sdar_costs.py``), over ``_gswiglu_kernel``'s device time inside
``decode_step``.  Pairs and empty experts are the ``decode`` spans'
``moe_held_pairs`` / ``moe_held_empty``.  Bound by bandwidth: about 64 rows
an expert (ridge: 240)."""
from perfbench.lib import scope_trace, sdar_costs as costs


def read(record):
    sizes = record.get("sdar")
    secs, execs = scope_trace.kernel_seconds(record, "_gswiglu_kernel")
    pairs, n = scope_trace.span_arg_sum(record, "decode", "moe_held_pairs")
    empty, _ = scope_trace.span_arg_sum(record, "decode", "moe_held_empty")
    if not sizes or not secs or not execs or not n or not record.get("peaks"):
        return None
    with_rows = int(sizes["num_hidden_layers"]) * int(sizes["num_experts"]) \
        - (empty or 0.0) / n
    return costs.roofline_share(
        costs.expert_gemm_flops(sizes, pairs / n),
        costs.expert_gemm_bytes(sizes, with_rows, pairs / n),
        secs / execs, record["peaks"])
