"""Kernels: the latent attend kernel's share of its roofline in decode:
the larger of (cache bytes of the live positions: one ``[ckv | k_rope]``
row a position and layer) / peak bytes/s and (score + value FLOPs for
every query head) / peak FLOP/s, over ``_latent_attn_kernel``'s device
time inside ``decode_step``.  Live positions are the ``decode`` spans'
``context_tokens`` over the traced window.  Bound by bandwidth at 64
heads a row (ridge: 240 FLOP/byte; the attend does 121)."""
from perfbench.lib import latent_costs, scope_trace


def read(record):
    sizes = record.get("latent")
    secs, execs = scope_trace.kernel_seconds(record, "_latent_attn_kernel")
    live, n = scope_trace.span_arg_sum(record, "decode", "context_tokens")
    if not sizes or not secs or not execs or not n or not record.get("peaks"):
        return None
    per_exec = live / n                # live positions a decode execution
    return latent_costs.roofline_share(
        latent_costs.latent_attend_flops(sizes, per_exec),
        latent_costs.latent_attend_bytes(sizes, per_exec),
        secs / execs, record["peaks"])
