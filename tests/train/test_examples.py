"""Smoke-run every example script with tiny settings (reference: the CI
jobs that execute example/ scripts nightly). Each must exit 0 and print
its progress lines."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                    os.pardir, os.pardir))

# the heaviest scripts (~15-25s each on the 1-core sweep box, per the
# mx.ledger tier-1 budget record) are slow-marked out of the tier-1
# filter; ci/run.sh train runs tests/train unfiltered so they stay
# covered every CI pass
CASES = [
    pytest.param(
        "image_classification/train_cifar10.py",
        ["--model", "mobilenet0.25", "--epochs", "1", "--batch-size",
         "32", "--steps-per-epoch", "3"], "epoch 0",
        marks=pytest.mark.slow),
    ("bert/pretrain.py",
     ["--config", "tiny", "--batch-size", "8", "--seq-len", "32",
      "--steps", "3"], "step 3"),
    ("bert/long_context.py",
     ["--dp", "2", "--sp", "2", "--seq-len", "64", "--steps", "2"],
     "step 2"),
    pytest.param(
        "bert/long_context.py",
        ["--dp", "2", "--sp", "2", "--pp", "2", "--seq-len", "64",
         "--steps", "2"], "step 2",
        marks=pytest.mark.slow),
    pytest.param(
        "gpt/pretrain.py",
        ["--config", "tiny", "--dp", "2", "--sp", "2", "--seq-len", "64",
         "--steps", "2"], "step 1",
        marks=pytest.mark.slow),
    ("gpt/generate.py",
     ["--steps", "60", "--merges", "40", "--max-new", "8"], "generated:"),
    pytest.param(
        "nmt/train_transformer.py",
        ["--steps", "20", "--batch-size", "8", "--seq-len", "5",
         "--units", "32"], "decode token accuracy",
        marks=pytest.mark.slow),
    pytest.param(
        "detection/train_yolo.py",
        ["--steps", "4", "--batch-size", "4"], "VOC07 mAP",
        marks=pytest.mark.slow),
    pytest.param(
        "timeseries/train_deepar.py",
        ["--epochs", "10", "--series", "8", "--samples", "5"], "CRPS",
        marks=pytest.mark.slow),
    ("module_api/train_mnist_module.py",
     ["--epochs", "2"], "final validation"),
    ("ocr/train_crnn.py",
     ["--steps", "12", "--batch", "8"], "held-out exact-match"),
]


@pytest.mark.parametrize(
    "script,args,expect", CASES,
    ids=[(c.values if hasattr(c, "values") else c)[0] for c in CASES])
def test_example_runs(script, args, expect):
    path = os.path.join(ROOT, "examples", script)
    r = subprocess.run(
        [sys.executable, path] + args,
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             # pinned explicitly: examples with --dp/--sp/--pp need the
             # 8-device virtual mesh even if a sibling test polluted the
             # inherited environment
             "XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
    assert r.returncode == 0, r.stderr[-2000:]
    assert expect in r.stdout, r.stdout[-2000:]
