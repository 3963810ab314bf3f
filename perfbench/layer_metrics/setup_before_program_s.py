"""Start-up: seconds from the process's start to the first statement of
``import deepspeed_tpu`` (the ledger's ``before_program`` row): the
interpreter, ``import jax``, ``jax.devices()`` — the box's share of
``setup_s``, which no change to the program moves.
``None`` on a program without the recorder."""
from perfbench.lib import startup_rows


def read(record):
    return startup_rows.read(record, "setup_before_program_s")
