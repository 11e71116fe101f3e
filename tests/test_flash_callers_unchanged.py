"""With ``Dv == D`` the traced program of every existing caller of
``flash_attention`` is the one it was (PR 42 gave the kernels a value head
size of their own, and ``SparseMoE`` a scope it reads off its config): the
jaxpr of each accepted LM cell's toy step, traced with the Pallas kernels on
as the chip traces them, against the digest taken on the parent's tree
(``tests/data/flash_callers_jaxpr_pr41.json``).

A digest, because the parent's kernels are 1,300 lines and not a function a
test can carry beside it, as ``tests/test_mellum.py`` carries ``rope``'s.
The text of a jaxpr holds no path and no line number (the same digests came
from two trees at two paths, under several ``PYTHONHASHSEED``s once the
frozensets of axis names are printed in sorted order), but it does follow
the installed JAX.  A PR
that changes these kernels ON PURPOSE takes the digests again on its own
tree (the function below, printed) and says so; a PR that meant to leave the
other cells alone and fails here did not.  PR 43 did, for the three MoE
cells: it gave ``ops/grouped_matmul.py``'s kernels their tile plan (the
file's note); the StarCoder cells' digests are PR 41's still.  PR 44 gave
``flash_attention`` its ``block_diffusion`` keyword and ``rope`` its
``positions`` and took NO digest again: the six are the parent's, and the
seventh is the new family's own toy step, taken on PR 44's tree.  PR 46
took ALL SEVEN again, on purpose and for a reason that is not the kernels':
the double buffer holds the matrices of ``pending`` in the wire's dtype, so
every cell's toy step has another state and another update (a select and
the wire cast at the state's write, a matrix).  Before taking them the seven
of PR 44's file were read to match on the parent's tree, and the two trees'
texts were read to differ by those primitives alone (the file's note).
PR 47 took ONE again, ``lfm2-8b-a1b-ep4share-t8192``'s, on purpose:
``LFM2MoE``'s layer puts its norms, the gated short convolution,
``silu(gate) * up`` and the plain QK-norm and rotation under
``jax.checkpoint`` (``models/lfm2.py::_between_products``), so its step
holds ``remat2`` equations; the six others are PR 46's and passed untouched
on PR 47's tree before and after."""

import hashlib
import json
import os
import re
import subprocess
import sys
import unittest.mock

import jax
import pytest

from chipbench import generator, spec, weights

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "data", "flash_callers_jaxpr_pr41.json")) as f:
    DIGESTS = {k: v for k, v in json.load(f).items() if k != "note"}


def toy_step_jaxpr(cell_name):
    """The text of the jaxpr of the cell's toy step, kernels on."""
    cell = spec.resolve(cell_name, rehearse=True)
    sizes = dict(cell.sizes, attention_impl="flash")
    plain = dict(sizes, attention_impl="xla")
    if "moe_matmul_impl" in sizes:
        sizes["moe_matmul_impl"] = "pallas"
        plain["moe_matmul_impl"] = "ragged_dot"
    comm = cell.family.make_comm(sizes, jax.devices()[:cell.chips])
    params = cell.family.make_params(plain, weights.seed_key(0, 0))
    step, state = cell.family.build(comm, sizes, params)
    ring = generator.make_ring(dict(sizes, ring=1), cell.chips,
                               weights.seed_key(0, 1), comm.mesh,
                               comm.data_axes)
    # only the trace: off the TPU a pallas_call inside shard_map cannot be
    # lowered, and the jaxpr is what is asked for
    with unittest.mock.patch.object(jax, "default_backend", lambda: "tpu"):
        text = str(step.trace(*state, ring[0]).jaxpr)
    # a checkpoint's policy prints as a function with its address (lfm2's
    # step since PR 47; no other cell's text holds one)
    text = re.sub(r"(<function \w+) at 0x[0-9a-f]+>", r"\1>", text)
    # a frozenset of names prints in the order of the process's string hashes
    return re.sub(r"frozenset\(\{([^}]*)\}\)", lambda found: "frozenset({"
                  + ", ".join(sorted(found.group(1).split(", "))) + "})", text)


def test_the_digests_are_of_the_seven_accepted_language_model_cells():
    bench = spec.load_benchmark()
    accepted = [w["name"] for w in bench["workloads"]
                if w["config"] in ("starcoderbase-1b", "lfm2-8b-a1b",
                                   "trinity-mini", "mellum2-12b",
                                   "sdar-30b-a3b")]
    assert sorted(accepted) == sorted(DIGESTS) and len(DIGESTS) == 7


@pytest.fixture(scope="module")
def traced_in_a_fresh_process():
    """``{cell: [digest, whether the text holds a pallas_call]}`` from a
    process of its own.  What a step traces to also follows what the
    process did before (with observability left enabled by another test the
    step gains its callbacks, for one), and under ``--dist loadfile`` which
    files share a worker goes by their run times: at PR 43 all six digests
    failed in one whole run of the tests and passed in the next."""
    done = subprocess.run(
        [sys.executable, "-c",
         "import conftest, test_flash_callers_unchanged as t; t.main()"],
        cwd=HERE, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
            [os.path.dirname(HERE), HERE])))
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.splitlines()[-1])


def main():
    traced = {}
    for cell_name in sorted(DIGESTS):
        text = toy_step_jaxpr(cell_name)
        traced[cell_name] = [hashlib.sha256(text.encode()).hexdigest(),
                             "pallas_call" in text]
    print(json.dumps(traced))


@pytest.mark.parametrize("cell_name", sorted(DIGESTS))
def test_a_callers_traced_step_is_the_parents(
        cell_name, traced_in_a_fresh_process):
    digest, has_kernels = traced_in_a_fresh_process[cell_name]
    assert has_kernels
    assert digest == DIGESTS[cell_name], (
        f"{cell_name}: the toy step with the kernels on no longer traces to "
        "the program its digest was taken of (tests/data/"
        "flash_callers_jaxpr_pr41.json)")
