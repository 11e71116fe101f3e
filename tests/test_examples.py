"""Example scripts run unchanged — the reference's end-user surface.

Reference strategy analogue (SURVEY.md §4): the examples ARE the contract
(`mpiexec -n N python train_*.py --communicator ...`); here each stock
script runs as a subprocess on the 8-device virtual CPU mesh with tiny
shapes.  MNIST is covered in test_training.py; these cover the rest of the
example tree (BASELINE.json configs 2-5's script shapes).
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args, timeout=420, base="examples"):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_NUM_CPU_DEVICES"] = "8"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, base, script), *args],
        capture_output=True, text=True, timeout=timeout, env=env)
    assert proc.returncode == 0, (
        f"{script} failed\nstdout:\n{proc.stdout[-2000:]}\n"
        f"stderr:\n{proc.stderr[-2000:]}")
    return proc.stdout


@pytest.mark.slow
def test_cifar_double_buffered(tmp_path):
    """VGG/CIFAR with the double-buffered optimizer (configs[2] shape)."""
    out = _run("cifar/train_cifar.py",
               "--epoch", "1", "--batchsize", "32", "--train-size", "256",
               "--double-buffering", "--dtype", "float32",
               "--out", str(tmp_path))
    assert "epoch" in out.lower() or "loss" in out.lower()


@pytest.mark.slow
def test_imagenet_tiny(tmp_path):
    """ImageNet script with a small arch + synthetic data (configs[1] shape)."""
    out = _run("imagenet/train_imagenet.py",
               "--arch", "nin", "--epoch", "1", "--batchsize", "16",
               "--train-size", "64", "--image-size", "64",
               "--n-classes", "10", "--dtype", "float32",
               "--out", str(tmp_path))
    assert "loss" in out.lower() or "epoch" in out.lower()


@pytest.mark.slow
def test_seq2seq_model_parallel():
    """Encoder/decoder on separate stages via send/recv (configs[3]);
    the synthetic default now runs the full NMT pipeline (vocab, length
    buckets, masked loss, greedy-decode BLEU)."""
    out = _run("seq2seq/seq2seq.py",
               "--epoch", "2", "--batchsize", "64", "--n-train", "256",
               "--seq-len", "8", "--hidden", "32")
    assert "token-acc" in out or "token_accuracy" in out
    assert "val_bleu" in out


@pytest.mark.slow
def test_seq2seq_file_corpus(tmp_path):
    """Reference parity (VERDICT round-2 'next #3'): train from parallel
    token-per-line text files with vocab construction, bucketing, masked
    loss, and held-out token-accuracy + BLEU."""
    import numpy as np

    rng = np.random.RandomState(0)
    words = ["uno", "dos", "tres", "cuatro", "cinco", "seis"]
    outs = ["one", "two", "three", "four", "five", "six"]
    src_lines, tgt_lines = [], []
    for _ in range(300):
        n = rng.randint(3, 9)
        idx = rng.randint(0, len(words), size=n)
        src_lines.append(" ".join(words[i] for i in idx))
        tgt_lines.append(" ".join(outs[i] for i in idx))
    (tmp_path / "train.src").write_text("\n".join(src_lines) + "\n")
    (tmp_path / "train.tgt").write_text("\n".join(tgt_lines) + "\n")
    out = _run("seq2seq/seq2seq.py",
               "--src", str(tmp_path / "train.src"),
               "--tgt", str(tmp_path / "train.tgt"),
               "--epoch", "10", "--batchsize", "32", "--hidden", "48",
               "--val-frac", "0.1")
    assert "val_bleu" in out and "val_token_accuracy" in out
    # word-for-word substitution over a 6-word vocab trains fast; the
    # metric must clearly beat chance (1/10 ids incl. specials)
    import re
    acc = float(re.search(r"'val_token_accuracy': ([\d.]+)", out).group(1))
    assert acc > 0.4, out


@pytest.mark.slow
def test_long_context_ring_attention():
    """Sequence-sharded LM training over ring attention (extension)."""
    out = _run("long_context/train_lm.py",
               "--attention", "ring", "--seq-len", "256", "--steps", "8",
               "--batchsize", "2", "--d-model", "64", "--layers", "1")
    assert "done in" in out


@pytest.mark.slow
def test_long_context_ring_flash():
    """Sequence-sharded LM with the fused per-block kernel (interpret mode
    on CPU; the compiled path is covered on TPU)."""
    out = _run("long_context/train_lm.py",
               "--attention", "ring_flash", "--seq-len", "256", "--steps",
               "4", "--batchsize", "2", "--d-model", "64", "--layers", "1")
    assert "done in" in out


@pytest.mark.slow
def test_moe_lm_trains_balanced():
    """Top-2 expert-parallel LM smoke: converges, reports routing stats,
    and no expert hoards the tokens during training.  (Aux-loss *efficacy*
    is pinned at unit level by test_aux_loss_gradient_pushes_toward_balance;
    this guards the end-to-end pipeline.)"""
    out = _run("moe_lm/train_moe_lm.py",
               "--steps", "16", "--batchsize", "8", "--seq-len", "128",
               "--d-model", "64", "--layers", "1", "--experts", "8",
               "--top-k", "2")
    assert "done in" in out
    last = [l for l in out.splitlines() if l.startswith("step ")][-1]
    # "load[min/max] a/b" — max below 0.5 means no expert hoards the tokens
    mx = float(last.rsplit("/", 1)[1])
    assert mx < 0.5, f"expert load collapsed: {last}"


@pytest.mark.slow
def test_parallel_convolution():
    """Channel-split conv demo (the reference's parallel_convolution)."""
    out = _run("parallel_convolution/train_parallel_conv.py",
               "--steps", "10", "--batchsize", "8")
    assert "loss" in out.lower() or "step" in out.lower()


@pytest.mark.slow
def test_imagenet_checkpoint_resume(tmp_path):
    """VERDICT round-2 'next #7': interrupted-and-resumed training must
    reproduce the uninterrupted trajectory.  Run A trains 2 epochs in one
    process; run B trains 1 epoch (snapshotting every epoch), is killed by
    exiting, restarts with --epoch 2, auto-resumes from the snapshot, and
    must land on run A's exact validation loss."""
    common = ["--arch", "nin", "--batchsize", "8", "--train-size", "128",
              "--image-size", "64", "--n-classes", "10", "--dtype",
              "float32", "--prefetch", "0", "--seed", "3"]

    def last_val_loss(out):
        rows = [l.split() for l in out.splitlines()
                if l.strip() and l.split()[0].isdigit()]
        assert rows, out
        return float(rows[-1][4])  # validation/loss column

    out_a = _run("imagenet/train_imagenet.py", *common, "--epoch", "2",
                 "--out", str(tmp_path / "a"))

    ck = str(tmp_path / "ck")
    out_b1 = _run("imagenet/train_imagenet.py", *common, "--epoch", "1",
                  "--checkpoint", ck, "--out", str(tmp_path / "b"))
    assert "resumed" not in out_b1
    out_b2 = _run("imagenet/train_imagenet.py", *common, "--epoch", "2",
                  "--checkpoint", ck, "--out", str(tmp_path / "b"))
    assert "resumed from snapshot" in out_b2

    # B2 only ran epoch 2; its final row must equal run A's epoch-2 row
    assert last_val_loss(out_b2) == pytest.approx(last_val_loss(out_a),
                                                  rel=1e-5)


@pytest.mark.slow
def test_imagenet_zero_optimizer(tmp_path):
    """--zero trains the ImageNet script with ZeRO-1 state sharding."""
    out = _run("imagenet/train_imagenet.py",
               "--arch", "nin", "--epoch", "1", "--batchsize", "16",
               "--train-size", "64", "--image-size", "64",
               "--n-classes", "10", "--dtype", "float32", "--zero",
               "--out", str(tmp_path))
    assert "loss" in out.lower() or "epoch" in out.lower()


@pytest.mark.slow
def test_imagenet_vit(tmp_path):
    """--arch vit_s16 trains through the stock ImageNet script (the
    MXU-shaped beyond-reference family, models/vit.py)."""
    out = _run("imagenet/train_imagenet.py",
               "--arch", "vit_s16", "--epoch", "1", "--batchsize", "16",
               "--train-size", "64", "--image-size", "32",
               "--n-classes", "10", "--dtype", "float32",
               "--out", str(tmp_path))
    assert "loss" in out.lower() or "epoch" in out.lower()


@pytest.mark.slow
def test_bench_vit_contract():
    """bench_vit.py emits its one-JSON-line contract on any backend."""
    import json

    stdout = _run("bench_vit.py", base="benchmarks")
    out = json.loads(stdout.strip().splitlines()[-1])
    assert out["unit"] == "images/sec/chip" and out["value"] > 0


@pytest.mark.slow
def test_imagenet_large_batch_recipe(tmp_path):
    """--optimizer lars --warmup-epochs + --accum-steps through the stock
    ImageNet script (the large-batch recipe knobs)."""
    out = _run("imagenet/train_imagenet.py",
               "--arch", "nin", "--epoch", "2", "--batchsize", "16",
               "--train-size", "64", "--image-size", "64",
               "--n-classes", "10", "--dtype", "float32",
               "--optimizer", "lars", "--warmup-epochs", "1",
               "--accum-steps", "2", "--out", str(tmp_path))
    assert "loss" in out.lower() or "epoch" in out.lower()


@pytest.mark.slow
def test_long_context_fsdp_matches_replicated():
    """--fsdp (ZeRO-3 over the sequence-parallel axis) reproduces the
    replicated run's loss trajectory exactly — same global objective,
    params/Adam state stored as 1/n_sp shards."""
    common = ["--attention", "ring", "--seq-len", "256", "--steps", "6",
              "--batchsize", "2", "--d-model", "64", "--layers", "1"]
    out_rep = _run("long_context/train_lm.py", *common)
    out_fsdp = _run("long_context/train_lm.py", *common, "--fsdp")

    def final(out):
        import re
        return float(re.search(r"final loss ([\d.]+)", out).group(1))

    assert final(out_fsdp) == pytest.approx(final(out_rep), rel=1e-4)


@pytest.mark.slow
def test_imagenet_fsdp_matches_plain_dp(tmp_path):
    """--fsdp (ZeRO-3 through the stock Trainer stack, FsdpUpdater)
    reproduces the plain-DP run: same seed, same final metrics."""
    common = ["--arch", "vit_s16", "--epoch", "2", "--batchsize", "8",
              "--train-size", "64", "--image-size", "32",
              "--n-classes", "8", "--dtype", "float32", "--seed", "5"]
    out_a = _run("imagenet/train_imagenet.py", *common,
                 "--out", str(tmp_path / "a"))
    out_b = _run("imagenet/train_imagenet.py", *common, "--fsdp",
                 "--out", str(tmp_path / "b"))

    import re

    def final_val_loss(out):
        return float(re.search(r"'validation/loss': ([\d.e+-]+)",
                               out).group(1))

    assert final_val_loss(out_b) == pytest.approx(final_val_loss(out_a),
                                                  rel=1e-4)


@pytest.mark.slow
def test_imagenet_fsdp_checkpoint_resume(tmp_path):
    """--fsdp + --checkpoint: the FsdpState snapshots and auto-resumes
    (interrupted run lands on the uninterrupted run's final metrics)."""
    common = ["--arch", "vit_s16", "--batchsize", "8", "--train-size",
              "64", "--image-size", "32", "--n-classes", "8", "--dtype",
              "float32", "--prefetch", "0", "--seed", "7", "--fsdp"]

    def last_val_loss(out):
        rows = [l.split() for l in out.splitlines()
                if l.strip() and l.split()[0].isdigit()]
        assert rows, out
        return float(rows[-1][4])

    out_a = _run("imagenet/train_imagenet.py", *common, "--epoch", "2",
                 "--out", str(tmp_path / "a"))
    ck = str(tmp_path / "ck")
    _run("imagenet/train_imagenet.py", *common, "--epoch", "1",
         "--checkpoint", ck, "--out", str(tmp_path / "b"))
    out_b2 = _run("imagenet/train_imagenet.py", *common, "--epoch", "2",
                  "--checkpoint", ck, "--out", str(tmp_path / "b"))
    assert "resumed from snapshot" in out_b2
    assert last_val_loss(out_b2) == pytest.approx(last_val_loss(out_a),
                                                  rel=1e-5)
