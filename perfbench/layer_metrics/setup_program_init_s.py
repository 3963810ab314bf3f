"""Start-up: seconds in the package's import and in the engines'
constructors (the ledger's ``package_import`` + ``engine_init`` rows,
counted once where they overlap): weights placed, pools allocated, state
sharded.
``None`` on a program without the recorder."""
from perfbench.lib import startup_rows


def read(record):
    return startup_rows.read(record, "setup_program_init_s")
