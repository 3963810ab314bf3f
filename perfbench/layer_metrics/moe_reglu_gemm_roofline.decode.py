"""Kernels: the grouped ReLU-gated product's share of its roofline in
decode, every expert held: the larger of (three H x F matrices of every
expert that got a row + the routed rows in and out) / peak bytes/s and (6 H
F FLOPs a routed pair) / peak FLOP/s (``lib/smallthinker_costs.py``), over
``_greglu_kernel``'s device time inside ``decode_step`` (all 8 layers).
Pairs and empty experts are the ``decode`` spans' ``moe_held_pairs`` /
``moe_held_empty``.  Bound by bandwidth: about six rows an expert.  ``None``
for a program without the kernel or the counters."""
from perfbench.lib import scope_trace, smallthinker_costs as costs


def read(record):
    sizes = record.get("smallthinker")
    secs, execs = scope_trace.kernel_seconds(record, "_greglu_kernel")
    pairs, n = scope_trace.span_arg_sum(record, "decode", "moe_held_pairs")
    empty, _ = scope_trace.span_arg_sum(record, "decode", "moe_held_empty")
    if not sizes or not secs or not execs or not n or not record.get("peaks"):
        return None
    with_rows = costs.expert_cells(sizes) - (empty or 0.0) / n
    return costs.roofline_share(
        costs.expert_gemm_flops(sizes, pairs / n),
        costs.expert_gemm_bytes(sizes, with_rows, pairs / n),
        secs / execs, record["peaks"])
