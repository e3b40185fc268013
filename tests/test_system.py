"""End-to-end behaviour tests for the AFL framework.

Covers: the full train driver (AFL LM training converges), serve driver
(prefill+decode), checkpoint resume through the driver path, and the
paper-claim smoke versions of the headline experiments."""
import numpy as np
import pytest

from repro.launch.serve import main as serve_main
from repro.launch.train import main as train_main


def test_train_driver_loss_decreases(tmp_path):
    final = train_main(["--arch", "gemma2-2b", "--reduced", "--d-model", "128",
                        "--layers", "2", "--vocab", "256", "--seq", "64",
                        "--batch", "8", "--steps", "120", "--algo", "ace",
                        "--n-clients", "4", "--lr-scale", "1.0",
                        "--log-every", "60",
                        "--ckpt-dir", str(tmp_path), "--ckpt-every", "60"]
                       ).loss
    # ~ln(256)+0.4 at init; must have made clear progress in 120 ACE steps
    assert final < 5.75


def test_train_driver_resumes_from_checkpoint(tmp_path):
    args = ["--arch", "yi-9b", "--reduced", "--d-model", "64", "--layers", "2",
            "--vocab", "128", "--seq", "32", "--batch", "2", "--algo", "aced",
            "--n-clients", "4", "--ckpt-dir", str(tmp_path),
            "--ckpt-every", "10", "--log-every", "50"]
    train_main(args + ["--steps", "10"])
    final = train_main(args + ["--steps", "20"]).loss   # resumes at 10
    assert np.isfinite(final)


@pytest.mark.parametrize("algo", ["ace", "fedbuff", "asgd"])
def test_train_driver_all_algorithms(algo):
    final = train_main(["--arch", "mamba2-780m", "--reduced",
                        "--d-model", "128", "--layers", "2", "--vocab", "128",
                        "--seq", "64", "--batch", "2", "--steps", "20",
                        "--algo", algo, "--n-clients", "4",
                        "--log-every", "20"]).loss
    assert np.isfinite(final)


def test_serve_driver_generates():
    gen = serve_main(["--arch", "zamba2-1.2b", "--reduced", "--batch", "2",
                      "--prompt-len", "8", "--gen", "8"])
    assert gen.shape == (2, 8)  # (batch, generated tokens)


def test_paper_claim_equal_comms_ace_beats_buffered():
    """App. E: at equal communication budget ACE out-converges FedBuff."""
    import sys, os
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.common import run_algo
    from repro.core.aggregators import ACEIncremental, FedBuff
    from repro.core.fl_tasks import make_vision_task
    task = make_vision_task(n_clients=20, alpha=0.3, n_train=3000,
                            n_test=800, dim=32, hidden=(64,), batch=10, seed=0)
    budget = 200
    ace = run_algo(task, lambda: ACEIncremental(), T=budget, beta=5.0,
                   lr=0.2 * np.sqrt(20 / budget), seeds=(1,))
    fb = run_algo(task, lambda: FedBuff(buffer_size=10), T=budget // 10,
                  beta=5.0, lr=1.0 * np.sqrt(20 / (budget // 10)), seeds=(1,))
    assert ace["acc_mean"] > fb["acc_mean"]
