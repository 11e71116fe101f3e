"""Test bootstrap.

Reference test strategy (SURVEY.md §4): tests run under a real multi-process
launcher (``mpiexec -n 2 pytest``) with no mocked backend.  The TPU-native
analogue is an 8-device virtual CPU mesh in one process — "mpiexec -n 8 on
one box" — over which every communicator runs real XLA collectives.

pytest imports this file before any test touches JAX, so the mesh is
configured the plain way: ``jax_platforms=cpu`` and ``jax_num_cpu_devices=8``
set before the first backend exists (``ensure_cpu_mesh``).  Never re-exec
here: pytest's fd-level capture is live when conftest runs, and an exec'd
replacement process would inherit the captured fds and lose all output.
"""

import os

# Tests never write the persistent compile cache (utils/compile_cache.py
# would place it inside the checkout): off for this process, before jax
# reads its environment, and for every subprocess that inherits it.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"

import jax  # noqa: E402

from chainermn_tpu.utils.cpu_mesh import ensure_cpu_mesh  # noqa: E402

ensure_cpu_mesh(8)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 CPU devices, got {len(devs)}"
    return devs[:8]


@pytest.fixture
def lint_step(devices):
    """The cmn-lint one-liner as a fixture: ``lint_step(step, *args,
    comm=..., ...)`` raises ``LintError`` on any error-severity finding
    (pass ``raise_on_error=False`` to inspect the report instead) — see
    docs/static_analysis.md."""
    from chainermn_tpu.analysis import lint_step as _lint_step

    return _lint_step


def pytest_collection_modifyitems(config, items):
    """Keep the default gate correctness-only: deselect ``perf``-marked
    timing thresholds unless the user asked for them via ``-m`` or by
    naming a test's node id.  (A plain path argument like
    ``pytest tests/test_transport.py`` still deselects them; an explicit
    ``::test_name`` runs exactly what was asked.)"""
    if config.option.markexpr:
        return  # user supplied -m: their expression governs
    if any("::" in a for a in config.args):
        return  # explicit node ids: run exactly what was named
    deselected = [i for i in items if "perf" in i.keywords]
    if deselected:
        config.hook.pytest_deselected(items=deselected)
        items[:] = [i for i in items if "perf" not in i.keywords]
