"""Kernels: the fused optimizer kernel's share of its HBM roofline: the
bytes one Adam update must move on this chip
(``perfbench/lib/flops.adam_step_bytes``: params, grads and both moments
read once, params and moments written once, at the widths of the
engine's state) over the chip's peak bytes/s, over the kernel's device
time per step.  Bound by bandwidth: the update does ~10 operations a
byte-pair, far under the chip's 240 FLOP/byte ridge."""


def read(record):
    tr = record.get("trace")
    if not tr or not record.get("steps_traced") or not record.get("peaks"):
        return None
    seconds = tr["op_seconds"].get("_fused_adam_kernel")
    if not seconds:
        return None
    floor = record["adam_bytes_per_step"] / record["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor / (seconds / record["steps_traced"])
