"""KV cache: of the prompt tokens the classes of PAGES had cached for the
window's admissions, the share that was prefilled again because the class of
STATES had no snapshot at that boundary (the ``prefill`` spans'
``prefix_lost_to_kind_tokens``, summed by the aggregator, over that sum +
the tokens admissions did take from the cache), in percent.  0 when every
hit the pages could serve had a snapshot at its boundary.  ``None`` for a
model whose cache is of one kind, or before any admission found pages."""


def read(record):
    w = record.get("sessions") or {}
    lost, cached = w.get("prefix_lost_to_kind_tokens"), w.get("cached_tokens")
    if lost is None or cached is None or not lost + cached:
        return None
    return 100.0 * lost / (lost + cached)
