"""CPU rehearsal of chip_smoke.py (on-chip-measurement guide, section 2,
rehearsal 1): every phase function end to end at GPT_TINY widths with the
kernels in interpret mode — wrong paths, arguments and control flow cost
no chip time — and the script's own platform check, which must stop a
run without a TPU before any phase and without a result line."""
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as CS  # noqa: E402

from paddle_tpu.models import gpt as G  # noqa: E402

TINY = G.GPT_TINY.scaled(num_layers=2, use_flash=True, remat=True,
                         remat_policy="dots")


def test_phase_train_rehearsal():
    CS.phase_train(TINY, 4, 64, 5, 0, jax.devices()[0])


def test_phase_serve_rehearsal():
    CS.phase_serve(TINY, dict(max_seq=64, max_batch=4,
                              weight_dtype="bf16"),
                   (20, 40, 8, 12, 33, 50), 16, 8, 8, 0, jax.devices()[0])


def test_phase_fluid_rehearsal():
    CS.phase_fluid(2, 32, 2, jax.devices()[0])


def test_phase_four_chips_rehearsal():
    CS.phase_four_chips(TINY, 4, 64, 3, 0, jax.devices())


@pytest.mark.parametrize("argv", [[], ["--four-chips"]],
                         ids=["one_chip", "four_chips"])
def test_stops_at_platform_check_without_tpu(argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO,
                                                        "chip_smoke.py")]
                          + argv, env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert "not a TPU" in proc.stderr
    assert proc.stdout.strip() == ""          # no phase ran, no result line
