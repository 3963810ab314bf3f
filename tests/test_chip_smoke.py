"""CPU rehearsal of chip_smoke.py: its phase functions at gpt2-tiny on
the host mesh (wrong paths, arguments and control flow are found here,
not on chip time), and the contract that ``main`` refuses anything but
a TPU."""
import os
import subprocess
import sys

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _lines(capsys):
    return [l for l in capsys.readouterr().out.splitlines()
            if l.startswith("{")]


def test_one_chip_phases_rehearse_on_cpu(tmp_path, monkeypatch, capsys):
    monkeypatch.setenv("DS_AUTOTUNE_REGISTRY", str(tmp_path / "reg.json"))
    cfg = chip_smoke.model_config("gpt2-tiny")
    chip_smoke.phase_kernels(cfg)
    ckpt = chip_smoke.phase_train(cfg, seed=0, workdir=str(tmp_path),
                                  steps=6)
    assert os.path.isfile(os.path.join(ckpt, "latest"))
    chip_smoke.phase_serve(cfg, seed=0, workdir=str(tmp_path), ckpt=ckpt,
                           n_requests=4)
    out = "\n".join(_lines(capsys))
    for phase in ("kernels", "train", "checkpoint", "serve",
                  "serve_compare"):
        assert f'"phase": "{phase}"' in out
    assert '"ok"' not in out            # only main prints the result


def test_four_chip_phase_rehearses_on_four_host_devices(tmp_path, capsys):
    cfg = chip_smoke.model_config("gpt2-tiny")
    chip_smoke.phase_four_chips(cfg, seed=0, workdir=str(tmp_path),
                                devices=jax.devices()[:4])
    out = "\n".join(_lines(capsys))
    assert '"arm": "dp4"' in out and '"arm": "dp4 vs dp1_gas4"' in out
    assert '"optimizer_moment_device_set_sizes": [4]' in out


def test_corpus_batches_follow_the_seed():
    a = chip_smoke.corpus_batches(0, 128, 4, 3)
    b = chip_smoke.corpus_batches(0, 128, 4, 3)
    c = chip_smoke.corpus_batches(1, 128, 4, 3)
    assert len(a) == 3 and a[0].shape == (4, 129)
    assert all((x == y).all() for x, y in zip(a, b))
    assert any((x != y).any() for x, y in zip(a, c))
    assert max(x.max() for x in a) < 256


def test_pallas_kernel_names_from_hlo_text():
    text = (
        '  %c.1 = bf16[8,128]{1,0} custom-call(%a), custom_call_target='
        '"tpu_custom_call", metadata={op_name="jit(train_step)/jit(main)/'
        '_ln_fwd_kernel/pallas_call" stack_frame_id=3}\n'
        '  %c.2 = f32[8,128]{1,0} custom-call(%b), custom_call_target='
        '"tpu_custom_call", metadata={op_name="jit(train_step)/while/body/'
        '_ln_fwd_kernel/pallas_call"}\n'
        '  %d = f32[8]{0} add(%x, %y)\n')
    assert chip_smoke.pallas_kernels(text) == {"_ln_fwd_kernel": 2}


def test_main_refuses_a_non_tpu_platform():
    """``python chip_smoke.py`` off-TPU: non-zero exit, no result line —
    with either option."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for extra in ([], ["--chips", "4"]):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "chip_smoke.py")] + extra,
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, timeout=600)
        assert out.returncode != 0, out.stdout[-2000:]
        assert '"ok": true' not in out.stdout
