"""Kernels: the sparse attend's share of its roofline in decode: the larger
of (one K/V head's K and V tiles of every block WALKED: the ``decode`` spans'
``sparse_blocks_read``, counted per stream, sparse layer, K/V head and
chosen block) / peak bytes/s and (score + value FLOPs of the head's 16 query
heads) / peak FLOP/s, over ``_pattn_kernel``'s device time inside
``decode_step``.  Bound by bandwidth (16 FLOPs a byte; ridge: 240).
``None`` where the program has no such counter."""
from perfbench.lib import minicpm_sala_costs as costs, scope_trace


def read(record):
    sizes = record.get("sala")
    secs, execs = scope_trace.kernel_seconds(record, "_pattn_kernel")
    read_, n = scope_trace.span_arg_sum(record, "decode",
                                        "sparse_blocks_read")
    if not sizes or not secs or not execs or not n or not record.get("peaks"):
        return None
    return costs.roofline_share(
        costs.attend_flops(sizes, read_ / n),
        costs.attend_bytes(sizes, read_ / n), secs / execs, record["peaks"])
