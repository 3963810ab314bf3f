"""Start-up: ``setup_s`` less everything the program's ledger accounts for
(the union of its ``before_program``, ``package_import``, ``engine_init``,
``warm_prefill_widths``, ``engine_traffic`` and ``own`` build rows): the
benchmark's own share — weight init, references and controls, traffic
files (their builds are the ``own`` 0 entries of the ``startup`` line).
``None`` on a program without the recorder."""
from perfbench.lib import startup_rows


def read(record):
    return startup_rows.read(record, "setup_outside_program_s")
