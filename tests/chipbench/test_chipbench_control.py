"""The comparison that decides ``correct`` fails what it must, at a size a
test run can hold (the cells' toy sizes, on the CPU):

* the CONTROL: the plain reference put in the program's place and computed
  one precision below the configuration's bfloat16 (int8) comes out as not
  correct under the limits (the toy sizes compute in float32, so that the
  program itself sits well inside them: the sound run below);
* a run with the timed path broken underneath (a step that returns its
  state unchanged; rows of the batch left out) ends in ``correct: false``.

The chip runs of the control at the cells' own sizes are in PERF.md.
"""

import json

import jax
import pytest

from chipbench import check, generator, harness, run, spec, weights

ONE_CHIP = ["resnet50-b256", "starcoder1b-t8192"]


def _batches(cell, seed):
    comm = cell.family.make_comm(cell.sizes, jax.devices()[:cell.chips])
    ring = generator.make_ring(dict(cell.sizes, ring=3), cell.chips,
                               weights.seed_key(seed, 1), comm.mesh,
                               comm.data_axes)
    return ring


@pytest.mark.parametrize("name", ONE_CHIP)
def test_the_control_in_int8_is_not_correct(name):
    cell = spec.resolve(name, rehearse=True)
    device = jax.devices()[:1]
    failed = []
    for seed in (101, 202, 303):
        batches = _batches(cell, seed)
        reference = harness.reference_readings(cell, seed, batches, device)
        control = harness.reference_readings(cell, seed, batches, device,
                                             "int8")
        rows, within = check.judge(check.numbers(control, reference),
                                   cell.limits)
        assert within is False, (seed, rows)
        failed.append({r["check"] for r in rows if not r["within"]})
    # the lower precision fails the gradient, the number set to catch it
    assert all("grad_norm" in names for names in failed)


def _main(monkeypatch, capsys, name, **patches):
    for attribute, value in patches.items():
        monkeypatch.setattr(harness.Run, attribute, value)
    code = run.main(["--workload", name, "--seed", "77", "--seconds", "1",
                     "--trace", "0", "--rehearse"])
    lines = [json.loads(line) for line in
             capsys.readouterr().out.splitlines() if line.startswith("{")]
    return code, lines


def test_a_sound_run_in_this_process_is_correct(monkeypatch, capsys):
    code, lines = _main(monkeypatch, capsys, ONE_CHIP[1])
    assert code == 0 and lines[-1]["correct"] is True


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch, capsys):
    import jax.numpy as jnp

    def call(self, batch):
        kept = jax.tree.map(jnp.copy, self.state)
        *_, loss = self.compiled(*self.state, batch)
        self.state = kept
        self.steps_taken += 1
        return loss

    code, lines = _main(monkeypatch, capsys, ONE_CHIP[1], call=call)
    assert code == 0 and lines[-1]["correct"] is False
    checks = {l["check"]: l for l in lines if l.get("phase") == "check"}
    assert checks["delta_norm"]["within"] is False
    assert checks["loss_step1"]["within"] is True


def test_rows_left_out_of_the_batch_are_not_correct(monkeypatch, capsys):
    import jax.numpy as jnp

    sound = harness.Run.call

    def call(self, batch):
        # every row replaced by the first: the rest of the batch is left out
        return sound(self, tuple(
            jnp.broadcast_to(leaf[:1], leaf.shape) for leaf in batch))

    code, lines = _main(monkeypatch, capsys, ONE_CHIP[0], call=call)
    assert code == 0 and lines[-1]["correct"] is False
    checks = {l["check"]: l for l in lines if l.get("phase") == "check"}
    assert checks["loss_step1"]["within"] is False
