"""KV cache: snapshots of the per-stream state the window's admissions left
in the prefix cache (the allocator's ``snapshots_taken``, the window's end
less its start) per admission that started in the window.  1 when every
turn leaves the boundary its session's next turn resumes at.  ``None`` for
a model that keeps no state beside its pages, or a window without
admissions."""


def read(record):
    w = record.get("sessions") or {}
    if not w.get("admissions") or w.get("snapshots_taken") is None:
        return None
    return w["snapshots_taken"] / w["admissions"]
