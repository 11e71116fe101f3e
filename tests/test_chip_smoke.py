"""``chip_smoke.py`` off the chip, and the compile-cache helper it places.

The script is the proof that the main path runs on an attached TPU, so what
can be pinned here is its contract off one: the CPU dress rehearsal runs the
same control flow to the end without ever naming a TPU, and the real command
fails where there is no accelerator instead of falling back.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _smoke(*args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_NUM_CPU_DEVICES", None)
    return subprocess.run([sys.executable, SMOKE, *args], env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=600)


def test_rehearsal_passes_on_cpu_and_never_names_a_tpu(tmp_path):
    r = _smoke("--rehearse", cwd=tmp_path)
    assert r.returncode == 0, r.stderr[-3000:]
    records = [json.loads(line) for line in r.stdout.splitlines()]
    assert records[0]["phase"] == "device" and records[0]["rehearsal"] is True
    assert [rec.get("phase") for rec in records[:-1]] == [
        "device", "setup", "resnet50", "lm_flash", "flash_parity"]
    last = records[-1]
    assert last["ok"] is True and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    assert "tpu" not in r.stdout.splitlines()[-1].lower()
    # a rehearsal's CPU programs are of no use to a chip run: no cache
    assert records[1]["compile_cache_dir"] is None


def test_without_an_accelerator_the_smoke_fails_and_prints_no_result(tmp_path):
    r = _smoke(cwd=tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no accelerator" in r.stderr


@pytest.fixture
def cache_dir_config():
    """``jax_compilation_cache_dir`` restored after the test."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_var_wins_and_nothing_is_set_in_code(
        monkeypatch, cache_dir_config, tmp_path):
    from chainermn_tpu.utils.compile_cache import place_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert place_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_one_fixed_place_in_the_checkout(
        monkeypatch, cache_dir_config):
    from chainermn_tpu.utils.compile_cache import place_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert place_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert place_compile_cache() == want    # the same path every time


def test_a_live_accelerator_backend_is_never_torn_down_for_a_cpu_mesh():
    """``ensure_device_count`` / ``ensure_cpu_mesh`` on a chip with too few
    devices raise instead of rebuilding on virtual CPU devices: a chip run
    that silently became a CPU run is a fallback that hides the device."""
    import unittest.mock as mock

    from chainermn_tpu.utils import cpu_mesh

    n = len(jax.devices()) + 1
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
            cpu_mesh.ensure_device_count(n)
        with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
            cpu_mesh.ensure_cpu_mesh(n)
    assert len(jax.devices()) == n - 1      # the backend is as it was
