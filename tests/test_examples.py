"""The examples/ scripts executed end-to-end — the reference's model-test
tier drives its example trainers as whole programs
(tests/model/run_func_test.py invokes the Megatron/BingBert scripts);
here each example runs as a real subprocess on the virtual CPU mesh and
must train to a finite, decreasing loss.

Kept honest by parsing the scripts' own stdout contract ("losses: ..." +
"final loss:"), not by importing their internals. Every example must not
just run — the first-quarter vs last-quarter window means of its printed
loss curve must DECREASE (the module's "finite, decreasing loss" claim;
the reference's func tests compare full loss curves).

The gpt2 flagship configs (ZeRO-2, ZeRO-Offload, 1-bit Adam, 1F1B
pipeline) train on REAL text — byte-level LM over the vendored
license-clean corpus (examples/data/corpus.txt, see its README) — with
loss-curve gates, closing an early review's top gap (every e2e example used
to train on synthetic random tokens). A byte-level model starts at the
ln(256) ~= 5.5 uniform floor and must cut into genuine English
statistics to pass.
"""
import os
import re
import subprocess
import sys

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # whole-module slow tier (see conftest)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CORPUS = os.path.join("examples", "data", "corpus.txt")


def run_example(rel, *args, timeout=420):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = flags + \
            " --xla_force_host_platform_device_count=8"
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, rel), *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO)
    assert p.returncode == 0, f"{rel} failed:\n{p.stdout}\n{p.stderr}"
    m = re.search(r"final (?:MLM )?loss:\s*([0-9.]+)", p.stdout)
    assert m, f"{rel} printed no final loss:\n{p.stdout[-2000:]}"
    c = re.search(r"losses:\s*([0-9. eE+-]+)", p.stdout)
    assert c, f"{rel} printed no loss curve:\n{p.stdout[-2000:]}"
    return float(m.group(1)), [float(x) for x in c.group(1).split()]


def assert_decreasing(losses, factor=0.97):
    """First-k vs last-k window means must drop by at least (1-factor):
    per-step curves are noisy, window means are the honest signal."""
    k = max(1, len(losses) // 4)
    first, last = np.mean(losses[:k]), np.mean(losses[-k:])
    assert last < factor * first, (first, last, losses)


def test_cifar_example_runs_and_learns():
    loss, curve = run_example("examples/cifar/train.py", "--steps", "60")
    assert loss < 2.3, loss            # below the ln(10) random floor
    assert_decreasing(curve)


def test_bert_example_learns():
    _, curve = run_example("examples/bert/train.py", "--steps", "48")
    assert_decreasing(curve)


def test_gpt2_example_zero2_real_text():
    loss, curve = run_example("examples/gpt2/train.py",
                              "--config", "ds_config_zero2.json",
                              "--data", CORPUS, "--steps", "24")
    assert curve[0] < 7.0                 # near the ln(256)~5.5 start
    assert loss < 5.0, loss               # well under the uniform floor
    assert_decreasing(curve, factor=0.85)


def test_gpt2_example_offload_real_text():
    loss, curve = run_example("examples/gpt2/train.py",
                              "--config", "ds_config_offload.json",
                              "--data", CORPUS, "--steps", "24")
    assert loss < 5.0, loss
    assert_decreasing(curve, factor=0.85)


def test_gpt2_example_onebit_real_text():
    loss, curve = run_example("examples/gpt2/train.py",
                              "--config", "ds_config_onebit.json",
                              "--data", CORPUS, "--steps", "48")
    assert loss < 5.0, loss
    assert_decreasing(curve, factor=0.85)


def test_gpt2_example_pipeline_1f1b_real_text():
    loss, curve = run_example("examples/gpt2/train.py",
                              "--config", "ds_config_pipeline.json",
                              "--pipeline", "--data", CORPUS,
                              "--steps", "24")
    assert loss < 5.0, loss
    assert_decreasing(curve, factor=0.85)
