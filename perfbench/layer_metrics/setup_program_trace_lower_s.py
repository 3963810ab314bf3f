"""Start-up: seconds of Python tracing and lowering of the engines' own
programs (``trace_s`` + ``lower_s`` of the ``own`` ``program_build`` rows
begun before the window): what no cache saves, paid at every start — a
further program, an unrolled kernel body or a traced copy shows here.
``None`` on a program without the recorder."""
from perfbench.lib import startup_rows


def read(record):
    return startup_rows.read(record, "setup_program_trace_lower_s")
