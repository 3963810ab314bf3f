"""Kernels: device self time of the trace's Pallas kernels (each
``pallas_call`` carries ``name=<kernel function>``, which ends in
``_kernel``; XLA names the custom call after it), per optimizer step,
device 0."""


def read(record):
    tr = record.get("trace")
    if not tr or not record.get("steps_traced"):
        return None
    total = sum(s for name, s in tr["op_seconds"].items()
                if name.endswith("_kernel"))
    return total / record["steps_traced"] * 1e3 if total else None
