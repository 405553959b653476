"""Examples stay runnable (reference ships example/ as living docs; these
smoke-run each script in a subprocess on the virtual CPU mesh)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args, timeout=420):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONPATH", None)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, script), *args],
        capture_output=True, text=True, timeout=timeout, env=env)
    assert proc.returncode == 0, (
        f"{script} failed:\nstdout:{proc.stdout[-2000:]}\n"
        f"stderr:{proc.stderr[-2000:]}")
    assert "OK" in proc.stdout
    return proc.stdout


def test_mnist_example():
    out = _run("example/gluon/train_mnist.py", "--epochs", "1",
               "--batch-size", "32")
    assert "accuracy=" in out


def test_spmd_resnet_example(tmp_path):
    out = _run("example/distributed_training/train_resnet_spmd.py",
               "--dp", "8", "--steps", "4", "--batch-size", "16",
               "--checkpoint-dir", str(tmp_path / "ck"))
    assert "mesh: dp=8" in out


def test_bert_elastic_example(tmp_path):
    out = _run("example/bert/pretrain_bert.py", "--tp", "2", "--dp", "4",
               "--steps", "4", "--checkpoint-dir", str(tmp_path / "ck"))
    assert "restarts" in out


def test_char_lm_example():
    out = _run("example/rnn/char_lm.py", "--steps", "45")
    assert "ppl" in out


def test_ssd_example():
    out = _run("example/ssd/train_ssd_toy.py", "--steps", "25",
               "--batch-size", "8", "--lr", "0.02")
    assert "detections kept" in out


# example/extensions/custom_op_ext.py is loaded (not executed) by
# tests/test_extensions.py — the MXLoadLib analog exercises it there.


def test_migration_example():
    out = _run("example/migration/import_mxnet_model.py")
    assert "MIGRATION_OK" in out


def test_adversary_example():
    out = _run("example/adversary/fgsm_mnist.py", "--epochs", "1")
    assert "adversarial accuracy" in out


def test_autoencoder_example():
    out = _run("example/autoencoder/conv_autoencoder.py", "--steps", "50")
    assert "recon_loss" in out


@pytest.mark.slow     # 38 s alone, 51 s beside three other workers (PR 28)
def test_bi_lstm_sort_example():
    # 140 biLSTM steps need ~6 min on the 1-core CI host and can exceed the
    # default budget when the host is also running other workers; the wider
    # timeout keeps this a completion test, not a speed test
    out = _run("example/bi-lstm-sort/bi_lstm_sort.py", "--steps", "140",
               timeout=900)
    assert "sorted-position accuracy" in out


def test_multi_task_example():
    out = _run("example/multi-task/multi_task_mnist.py", "--steps", "80")
    assert "parity accuracy" in out


def test_recommenders_example():
    out = _run("example/recommenders/matrix_fact.py", "--steps", "200")
    assert "RMSE" in out


def test_rbm_example():
    out = _run("example/restricted-boltzmann-machine/binary_rbm.py",
               "--epochs", "2")
    assert "recon_err" in out


def test_vae_example():
    out = _run("example/probability/vae.py", "--steps", "100")
    assert "library KL" in out


def test_profiler_example():
    out = _run("example/profiler/profile_matmul.py", "--iters", "10")
    assert "trace:" in out


def test_amp_example():
    out = _run("example/automatic-mixed-precision/amp_tutorial.py",
               "--steps", "50")
    assert "converted-model relative error" in out


def test_multi_threaded_inference_example():
    out = _run("example/multi_threaded_inference/multi_threaded_inference.py",
               "--threads", "3", "--iters", "4")
    assert "bit-identical" in out


def test_horovod_style_example():
    out = _run("example/distributed_training-horovod/"
               "train_horovod_style.py", "--steps", "60")
    assert "horovod-style kvstore: rank 0/" in out


def test_quantization_example():
    out = _run("example/quantization/quantize_digits.py")
    assert "top-1 agreement" in out
