"""The stock examples named by BASELINE's config list, run for real via the
launcher († ``test/integration/test_static_run.py`` runs the reference's
examples under ``horovodrun`` the same way):

- ResNet-50 ImageNet, torch ``DistributedOptimizer`` data-parallel
  († ``examples/pytorch/pytorch_imagenet_resnet50.py``)
- BERT masked-LM pretraining, TF Keras callbacks
  († BASELINE config "BERT-Large pretraining (TF Keras hvd callback)")

Tiny shapes, 2 real processes, CPU platform (the dev rig).
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _hvdrun_example(script_args, timeout=420):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
         "--platform", "cpu", "--", sys.executable] + script_args,
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO)


@pytest.mark.integration
@pytest.mark.slow  # tier-1 budget (~30s): CI examples-smoke runs every example
def test_torch_imagenet_resnet50_example():
    res = _hvdrun_example(
        [os.path.join(REPO, "examples", "torch_imagenet_resnet50.py"),
         "--epochs", "1", "--steps-per-epoch", "1", "--image-size", "32",
         "--batch-size", "2", "--num-classes", "10",
         "--batches-per-allreduce", "2"])
    assert res.returncode == 0, res.stdout + res.stderr
    assert "DONE resnet50" in res.stdout


@pytest.mark.integration
@pytest.mark.slow  # tier-1 budget (~28s): CI examples-smoke runs every example
def test_tf_keras_bert_pretrain_example():
    res = _hvdrun_example(
        [os.path.join(REPO, "examples", "tf_keras_bert_pretrain.py"),
         "--epochs", "1", "--samples", "16", "--batch-size", "8"])
    assert res.returncode == 0, res.stdout + res.stderr
    assert "DONE bert" in res.stdout


@pytest.mark.integration
def test_llama_moe_example():
    """Expert-parallel MoE Llama (use_moe=True, ep=2) trains real steps
    under the launcher at np=2 — the acceptance smoke for the MoE
    workload the autoscale scenario resizes."""
    res = _hvdrun_example(
        [os.path.join(REPO, "examples", "llama_moe.py")])
    assert res.returncode == 0, res.stdout + res.stderr
    # world size = 2 procs x inherited local device count; ep stays 2.
    assert "DONE moe rank=0/" in res.stdout, res.stdout
    assert "ep=2" in res.stdout, res.stdout


@pytest.mark.integration
def test_llama_serve_example():
    """Single-process serving example: continuous batching end to end."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "llama_serve.py"),
         "--platform", "cpu", "--requests", "3", "--max-active", "2"],
        capture_output=True, text=True, timeout=420, env=env, cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "per-request results" in res.stdout
    assert res.stdout.count("ttft") >= 3
