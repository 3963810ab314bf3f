"""Start-up: programs of the engines' own built, loaded or deserialised
before the window (the ``own`` ``program_build`` rows): a further program
or prefill width shows as +1.
``None`` on a program without the recorder."""
from perfbench.lib import startup_rows


def read(record):
    return startup_rows.read(record, "setup_programs_built")
