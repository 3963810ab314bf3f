"""Kernels: device self time of the paged-attention kernel
(``_pattn_kernel``) inside executions of the ``decode_step`` program on
device 0, per execution (the kernel also runs in ``prefill_step``, which
is not counted here)."""


def read(record):
    tr = record.get("trace")
    if not tr or record.get("kind") != "serve":
        return None
    seconds = iters = 0
    for module, ops in tr["op_seconds_by_module"].items():
        if "decode_step" in module:
            seconds += ops.get("_pattn_kernel", 0.0)
            iters += tr["modules"].get(module, 0)
    if not seconds or not iters:
        return None
    return seconds / iters * 1e3
