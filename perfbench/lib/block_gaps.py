"""The time between a stream's consecutive BLOCKS in a traced window, for a
model generated by diffusion over blocks: the ``emit`` spans'
``block_gaps_ms`` (``inference/scheduler.py``: one gap, in ms, a stream
handed a block in that row of the timeline, since its block before, joined
by spaces).  A program whose ``emit`` spans carry no such arg gives nothing.
"""
from perfbench.lib import program_trace


def gaps_ms(record) -> list:
    """Every gap of the traced window, ascending ([] where there is none)."""
    tr = program_trace.current(record)
    out = []
    for _, _, a in (tr or {"spans": {}})["spans"].get("emit", []):
        out += [float(g) for g in str(a.get("block_gaps_ms") or "").split()]
    return sorted(out)


def percentile(values: list, q: float):
    """Nearest rank over ascending ``values`` (``monitor/serving.py``'s
    rule), ``None`` for none."""
    if not values:
        return None
    return values[max(0, min(len(values) - 1,
                             int(round(q / 100.0 * (len(values) - 1)))))]
