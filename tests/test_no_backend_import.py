"""One process per chip: importing the package, the launcher or the
serving tier must not initialise a JAX backend (on a TPU host that takes
the chip), and neither may the runner's local-resource path — the worker
it spawns needs the chip."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import importlib, sys
from jax._src import xla_bridge
importlib.import_module({module!r})
{extra}
assert not xla_bridge._backends, dict(xla_bridge._backends)
print("clean")
"""


def _run(module, extra=""):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    return subprocess.run(
        [sys.executable, "-c", _PROBE.format(module=module, extra=extra)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, timeout=300)


@pytest.mark.parametrize("module", [
    "deepspeed_tpu", "deepspeed_tpu.launcher.runner",
    "deepspeed_tpu.launcher.launch", "deepspeed_tpu.inference"])
def test_import_initialises_no_backend(module):
    out = _run(module)
    assert out.returncode == 0 and "clean" in out.stdout, out.stdout[-2000:]


def test_runner_local_chip_count_leaves_parent_without_backend():
    out = _run("deepspeed_tpu.launcher.runner",
               "from deepspeed_tpu.launcher.runner import local_chip_count\n"
               "n = local_chip_count()\n"
               "assert n >= 1, n")
    assert out.returncode == 0 and "clean" in out.stdout, out.stdout[-2000:]
