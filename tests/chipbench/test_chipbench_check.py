"""chipbench/check.py and the reference's three-step follower, on numbers
small enough to do by hand."""

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check
from chipbench.references import common


def test_worst_leaf_gap_is_measured_against_the_larger_of_leaf_and_median():
    reference = np.array([1.0, 2.0, 0.001, 4.0, 8.0])      # median 2.0
    program = np.array([1.0, 2.2, 0.101, 4.0, 8.0])
    gap, leaf = check.worst_leaf_gap(program, reference)
    # leaf 1: 0.2 / 2.0; leaf 2 is all but zero: 0.1 / median 2.0, not / 0.001
    assert leaf == 1 and gap == pytest.approx(0.1)
    gap, leaf = check.worst_leaf_gap(np.array([1.0, np.nan]),
                                     np.array([1.0, 1.0]))
    assert gap == float("inf") and leaf == 1
    assert check.worst_leaf_gap(np.ones(3), np.ones(4))[0] == float("inf")


def test_every_number_stands_beside_its_limit():
    reference = {"losses": [2.0, 2.0, 1.0], "grad_norms": np.ones(4),
                 "delta_norms": np.ones(4)}
    program = {"losses": [2.0, 2.002, 1.0], "grad_norms": np.ones(4) * 1.01,
               "delta_norms": np.zeros(4)}          # a state that never moved
    rows, within = check.judge(check.numbers(program, reference),
                               {"loss": 1e-4, "grad_norm": 0.02,
                                "delta_norm": 0.5})
    by_name = {row["check"]: row for row in rows}
    assert not within
    assert [by_name[n]["within"] for n in (
        "loss_step1", "loss_step2", "loss_step3", "grad_norm",
        "delta_norm")] == [True, False, True, True, False]
    assert by_name["delta_norm"]["value"] == pytest.approx(1.0)
    assert by_name["loss_step2"]["limit"] == 1e-4
    broken = dict(program, losses=[float("nan"), 2.0, 1.0])
    assert check.numbers(broken, reference)["loss_step1"]["value"] == float(
        "inf")


@pytest.mark.parametrize("double_buffering", [False, True])
def test_three_steps_of_sgd_by_hand(double_buffering):
    """loss = 0.5 * w**2 * scale of the batch: gradient w * scale."""
    def loss(params, batch):
        (scale,) = batch
        return 0.5 * jnp.sum(params["w"] ** 2) * jnp.mean(scale)

    lr, mu = 0.1, 0.9
    batches = [(jnp.full((2,), s),) for s in (1.0, 2.0, 3.0)]
    out = common.follow_three_steps(
        loss, lambda: {"w": jnp.array([1.0, -2.0])}, batches,
        {"rule": "sgd", "learning_rate": lr, "momentum": mu,
         "double_buffering": double_buffering},
        devices=[jnp.zeros(()).devices().pop()])
    w0 = np.array([1.0, -2.0])
    if double_buffering:       # the first update applies zeros
        g1, g2 = w0 * 1.0, w0 * 2.0
        w2 = w0 - lr * g1
        w3 = w2 - lr * (mu * g1 + g2)
        losses = [0.5 * 5 * 1, 0.5 * 5 * 2, 0.5 * np.sum(w2 ** 2) * 3]
    else:
        g1 = w0 * 1.0
        w1 = w0 - lr * g1
        m2 = mu * g1 + w1 * 2.0
        w2 = w1 - lr * m2
        m3 = mu * m2 + w2 * 3.0
        w3 = w2 - lr * m3
        losses = [0.5 * 5 * 1, 0.5 * np.sum(w1 ** 2) * 2,
                  0.5 * np.sum(w2 ** 2) * 3]
    assert out["losses"] == pytest.approx(losses, rel=1e-6)
    assert out["grad_norms"] == pytest.approx([np.linalg.norm(g1)], rel=1e-6)
    assert out["delta_norms"] == pytest.approx(
        [np.linalg.norm(w3 - w0)], rel=1e-6)


def test_int8_rounds_operands_and_gradients_and_float32_does_not():
    a = jnp.linspace(-1.0, 1.0, 12).reshape(3, 4)
    b = jnp.linspace(0.5, 2.0, 8).reshape(4, 2)
    exact = np.asarray(common.Products("float32").dot(a, b))
    rounded = np.asarray(common.Products("int8").dot(a, b))
    assert np.allclose(exact, np.asarray(a) @ np.asarray(b), rtol=1e-6)
    assert 1e-4 < np.max(np.abs(rounded - exact)) < 0.05
