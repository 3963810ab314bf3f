"""Start-up: seconds the engine served traffic before the window — the
warm-up requests or steps and the contexts a runner builds in set-up (the
ledger's ``engine_traffic`` rows: a ``serve()`` call, a ``train_batch``
call to its step's completion) — less the builds inside them.
``None`` on a program without the recorder."""
from perfbench.lib import startup_rows


def read(record):
    return startup_rows.read(record, "setup_engine_traffic_s")
