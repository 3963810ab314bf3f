"""Serving engine, a model generated by diffusion over blocks: 99th
percentile of the time between a stream's consecutive blocks over the
traced window (the ``emit`` spans' ``block_gaps_ms``,
``lib/block_gaps.py``): a block that waited out other requests' admissions.
``None`` on a program whose ``emit`` spans carry no such arg."""
from perfbench.lib import block_gaps


def read(record):
    return block_gaps.percentile(block_gaps.gaps_ms(record), 99)
