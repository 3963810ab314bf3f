"""Serving engine, a model generated by diffusion over blocks: median time
between a stream's consecutive blocks over the traced window (the ``emit``
spans' ``block_gaps_ms``, ``lib/block_gaps.py``).  What a user of such a
model feels in place of the gap between tokens, which is 0 inside a block.
``None`` on a program whose ``emit`` spans carry no such arg."""
from perfbench.lib import block_gaps


def read(record):
    return block_gaps.percentile(block_gaps.gaps_ms(record), 50)
