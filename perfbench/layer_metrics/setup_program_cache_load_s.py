"""Start-up: seconds the engines' own programs took to come out of the
persistent compile cache or a kept executable (``backend_s`` of the
``own`` ``program_build`` rows with ``source`` ``compile_cache`` /
``kept_executable``): the warm start's floor a program; 0.0 on a cold
one.
``None`` on a program without the recorder."""
from perfbench.lib import startup_rows


def read(record):
    return startup_rows.read(record, "setup_program_cache_load_s")
