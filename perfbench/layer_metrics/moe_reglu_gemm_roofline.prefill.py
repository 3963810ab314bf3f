"""Kernels: the grouped ReLU-gated product's share of its roofline in a
prefill chunk: the same two floors as ``moe_reglu_gemm_roofline.decode``
over ``_greglu_kernel``'s device time inside ``prefill_step`` (all 8
layers), per execution.  Pairs and experts with a row of a MEAN chunk
program from the ``prefill`` spans (``chunks``, ``slots``, ``moe_held_pairs``,
``moe_held_empty``: ``lib/smallthinker_costs.prefill_expert_load``).  At 48
rows an expert the weights still outweigh the products (ridge ~240 rows).
``None`` for a program without the kernel or the counters."""
from perfbench.lib import program_trace, scope_trace, smallthinker_costs as costs


def read(record):
    sizes = record.get("smallthinker")
    secs, execs = scope_trace.kernel_seconds(record, "_greglu_kernel",
                                             program="prefill_step")
    tr = program_trace.current(record)
    if not sizes or not secs or not execs or tr is None \
            or not record.get("peaks"):
        return None
    load = costs.prefill_expert_load(sizes, tr["spans"].get("prefill", []),
                                     int(record["prefill_chunk"]))
    if load is None:
        return None
    pairs, with_rows = load
    return costs.roofline_share(
        costs.expert_gemm_flops(sizes, pairs),
        costs.expert_gemm_bytes(sizes, with_rows, pairs),
        secs / execs, record["peaks"])
