"""Kernels: ``moe_expert_gemm_roofline``'s twin for a program of the
``kimi_linear`` family.  That reader takes the expert layers as
``num_hidden_layers - first_k_dense_replace`` of ``record["latent"]``, the
dict whose ``num_hidden_layers`` this family's runner has to give as its
LATENT layers (``lib/latent_costs.latent_attend_bytes`` multiplies a row's
bytes by it); this one takes the expert layers and the experts held from
keys of their own, ``record["kda"]["moe_layers"]`` / ``["experts_held"]``,
and the widths from ``record["latent"]``: the larger of (three H x F
matrices of every held expert that got a row + the routed rows in and out)
/ peak bytes/s and (6 H F FLOPs a routed pair) / peak FLOP/s, over
``_gswiglu_kernel``'s device time inside ``decode_step``.  Pairs and empty
experts are the ``decode`` spans' ``moe_held_pairs`` / ``moe_held_empty``.
Bound by bandwidth: about eight rows an expert.  ``None`` for a program
without the keys."""
from perfbench.lib import latent_costs, scope_trace


def read(record):
    sizes, kda = record.get("latent"), record.get("kda") or {}
    secs, execs = scope_trace.kernel_seconds(record, "_gswiglu_kernel")
    pairs, n = scope_trace.span_arg_sum(record, "decode", "moe_held_pairs")
    empty, _ = scope_trace.span_arg_sum(record, "decode", "moe_held_empty")
    if not sizes or "moe_layers" not in kda or not secs or not execs \
            or not n or not record.get("peaks"):
        return None
    with_rows = int(kda["moe_layers"]) * int(kda["experts_held"]) \
        - (empty or 0.0) / n
    return latent_costs.roofline_share(
        latent_costs.expert_gemm_flops(sizes, pairs / n),
        latent_costs.expert_gemm_bytes(sizes, with_rows, pairs / n),
        secs / execs, record["peaks"])
