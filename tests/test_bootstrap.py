"""Bootstrap TPU-detection tests.

The no-arg pod path of ``init_distributed`` must fire on standard Cloud
TPU hosts where ``JAX_PLATFORMS`` is unset and the TPU plugin is
auto-discovered — detection comes from slice-metadata env (ADVICE round-1
medium finding). Pure env-logic tests; no backend is touched.
"""

import pytest

from chainermn_tpu.runtime.bootstrap import _tpu_metadata_present


@pytest.mark.parametrize("var", [
    "TPU_WORKER_HOSTNAMES", "TPU_WORKER_ID", "CLOUD_TPU_TASK_ID",
    "TPU_ACCELERATOR_TYPE",
])
def test_metadata_env_detected(monkeypatch, var):
    for v in ("TPU_WORKER_HOSTNAMES", "TPU_WORKER_ID", "CLOUD_TPU_TASK_ID",
              "TPU_SKIP_MDS_QUERY", "TPU_ACCELERATOR_TYPE"):
        monkeypatch.delenv(v, raising=False)
    monkeypatch.setenv(var, "v5e-8" if "TYPE" in var else "0")
    assert _tpu_metadata_present()


def test_no_metadata_means_not_tpu(monkeypatch):
    """No slice-metadata env => not a TPU pod host, even if the libtpu
    wheel happens to be installed (a dev box with jax[tpu] must not probe
    the GCE metadata server)."""
    for v in ("TPU_WORKER_HOSTNAMES", "TPU_WORKER_ID", "CLOUD_TPU_TASK_ID",
              "TPU_SKIP_MDS_QUERY", "TPU_ACCELERATOR_TYPE"):
        monkeypatch.delenv(v, raising=False)
    assert not _tpu_metadata_present()


def test_skip_mds_query_alone_is_not_slice_metadata(monkeypatch):
    """``import jax`` sets TPU_SKIP_MDS_QUERY itself on a host where it
    finds no chip, so that variable says nothing about a slice."""
    for v in ("TPU_WORKER_HOSTNAMES", "TPU_WORKER_ID", "CLOUD_TPU_TASK_ID",
              "TPU_ACCELERATOR_TYPE"):
        monkeypatch.delenv(v, raising=False)
    monkeypatch.setenv("TPU_SKIP_MDS_QUERY", "1")
    assert not _tpu_metadata_present()


@pytest.fixture
def pod_env(monkeypatch):
    """The no-argument pod path on a TPU platform."""
    for v in ("CHAINERMN_TPU_COORDINATOR", "CHAINERMN_TPU_NUM_PROCESSES",
              "CHAINERMN_TPU_PROCESS_ID", "TPU_WORKER_HOSTNAMES",
              "MEGASCALE_COORDINATOR_ADDRESS"):
        monkeypatch.delenv(v, raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    return monkeypatch


def test_declared_single_host_initializes_nothing(pod_env):
    """A slice of one host (what a sealed v5e host's environment says:
    TPU_WORKER_HOSTNAMES=localhost) is single-controller by construction:
    ``jax.distributed.initialize()`` is not called at all — on a host
    without network it can only probe a metadata server it cannot
    reach."""
    import unittest.mock as mock

    from chainermn_tpu.runtime.bootstrap import init_distributed

    pod_env.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    with mock.patch("jax.distributed.initialize") as init:
        init_distributed()
    init.assert_not_called()


@pytest.mark.parametrize("hostnames", ["host-a,host-b", None],
                         ids=["multi_host", "undeclared"])
@pytest.mark.parametrize("err", [
    RuntimeError("jax.distributed.initialize() must be called before any "
                 "JAX calls that might initialise the XLA backend."),
    ConnectionError("metadata.google.internal: name resolution failed"),
    ValueError("coordinator_address should be defined."),
], ids=["late_call", "no_metadata_server", "no_coordinator"])
def test_multi_host_bootstrap_failure_raises(pod_env, hostnames, err):
    """On a multi-host slice — or one whose worker list is not declared
    in the environment — a bootstrap that does not come up must raise:
    each host silently going on as its own single-controller world would
    train divergent models."""
    import unittest.mock as mock

    from chainermn_tpu.runtime.bootstrap import init_distributed

    if hostnames:
        pod_env.setenv("TPU_WORKER_HOSTNAMES", hostnames)
    with mock.patch("jax.distributed.initialize", side_effect=err):
        with pytest.raises(type(err)):
            init_distributed()


def test_already_initialized_is_benign(pod_env):
    import unittest.mock as mock

    from chainermn_tpu.runtime.bootstrap import init_distributed

    pod_env.setenv("TPU_WORKER_HOSTNAMES", "host-a,host-b")
    with mock.patch("jax.distributed.initialize",
                    side_effect=RuntimeError("already initialized")):
        init_distributed()


def test_cpu_platform_suppresses_pod_path(monkeypatch):
    """Even with TPU metadata present, an explicit JAX_PLATFORMS=cpu run
    (the test environment itself) must stay single-controller."""
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    # replicate init_distributed's gate expression
    import os

    platforms = os.environ.get("JAX_PLATFORMS") or ""
    fire = "tpu" in platforms or (
        "cpu" not in platforms and _tpu_metadata_present())
    assert not fire
