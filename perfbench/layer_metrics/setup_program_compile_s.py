"""Start-up: seconds the backend compiled the engines' own programs
(``backend_s`` of the ``own`` ``program_build`` rows with ``source``
``compiled``: a compile-cache miss): a cold start; 0.0 on a warm one.
``None`` on a program without the recorder."""
from perfbench.lib import startup_rows


def read(record):
    return startup_rows.read(record, "setup_program_compile_s")
