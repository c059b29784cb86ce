"""``correct`` comes out false when the timed path is broken underneath,
and for the control: on the CPU, at the registry's ``reduced()`` sizes,
with each one-chip cell's own limits.

The run skips the harness's look for a chip and otherwise drives a whole
run.  The faults a federated training cell can have, planted in the
program from outside:

  frozen       the round program returns the state it was given;
  half_batch   the round program sees the first half of the private rows
               (its mean is then taken over the rest);
  no_exchange  the Eq.-2 term receives nothing from the other clients
               (in the one-chip round and in the sharded one).

A training cell produces no tokens or answers to alter, so that fault
does not apply.  The control is the reference itself computed with
float8 operands, compared with the float32 reference as a run compares
the program."""
import pytest

import chipbench_cells as cells
from benchmarks.chip import correct, harness
from benchmarks.chip.reference import dml as ref_dml

SEED = 2 ** 31 + 21


def _wrap_step(pop, change):
    make = pop._dml_step

    def broken(*args):
        step = make(*args)
        return lambda params, opt, tokens, pub, **kw: change(
            step, params, opt, tokens, pub, **kw)
    pop._dml_step = broken


def frozen(pop, fed, monkeypatch):
    def same_state(step, params, opt, tokens, pub, **kw):
        return (params, opt) + tuple(step(params, opt, tokens, pub, **kw)[2:])
    _wrap_step(pop, same_state)


def half_batch(pop, fed, monkeypatch):
    def half(step, params, opt, tokens, pub, **kw):
        return step(params, opt, tokens[:, : tokens.shape[1] // 2], pub, **kw)
    _wrap_step(pop, half)


def no_exchange(pop, fed, monkeypatch):
    import jax.numpy as jnp
    from repro.core import distributed as D
    monkeypatch.setattr(D, "_mutual_term", lambda flat, *a, **kw:
                        jnp.zeros(flat.shape[:1], jnp.float32))
    monkeypatch.setattr(D.ops, "mutual_kl_pair", lambda live, *a, **kw:
                        jnp.zeros(live.shape[:2], jnp.float32))


FAULTS = {"frozen": frozen, "half_batch": half_batch,
          "no_exchange": no_exchange}

# a sound float32 run reads every number under 1e-4 here
# (test_chipbench_reference.py); without a cell limit, a fault must read
# a hundred times that on some number
SOUND = 1e-4


def broken(checks) -> bool:
    return any(not c["value"] <= (c["limit"] if c["limit"] is not None
                                  else 100 * SOUND)
               for c in checks.values())


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", cells.CELLS)
def test_a_broken_round_is_not_correct(cell, fault, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "ref")
    cells.on_one_device(monkeypatch)
    out = harness.run(cells.tiny(cell), SEED, 0.05, False, on_chip=False,
                      after_build=lambda pop, fed:
                      FAULTS[fault](pop, fed, monkeypatch))
    assert out["correct"] is False
    assert broken(out["checks"]), out["checks"]


@pytest.mark.parametrize("cell", cells.CELLS)
def test_the_control_is_not_correct(cell):
    c = cells.tiny(cell)
    ref = ref_dml.Federation(c.family, c.config, c.traffic).run(
        SEED, correct.STEPS)
    ctl = ref_dml.Federation(c.family, c.config, c.traffic,
                             precision="fp8").run(SEED, correct.STEPS)
    ok, checks = correct.decide(correct.numbers(ctl, ref), c.limits)
    assert ok is False and broken(checks), checks
