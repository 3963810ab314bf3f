"""KV cache: state snapshots the allocator evicted in the window (pages of
the per-stream class taken back from the prefix cache to make room) over the
window's admissions, from the state allocator's running totals
(``StateAllocator.snapshot_totals()["snapshots_evicted"]`` at the window's end
less its value at the start).  Beside ``serve_snapshots_per_admit`` it says
whether the snapshot pool turns over; beside ``serve_prefix_kind_loss``
whether what it pushed out was wanted again.  ``None`` for a runner that
records no such totals."""


def read(record):
    w = record.get("sessions") or {}
    if not w.get("admissions") or w.get("snapshots_evicted") is None:
        return None
    return w["snapshots_evicted"] / w["admissions"]
