"""The plain float32 reference against the program, on the CPU at the
registry's ``reduced()`` sizes, through the harness's own run.

Both sides compute in float32 here, so every compared number must be
small: the two differ only in the order of reductions (tolerance 1e-4,
about a thousand float32 roundings of a loss near ln V).  The program
runs its kernels as the XLA reference graph (``impl="ref"``) and in the
Pallas interpreter (``impl="interpret"``)."""
import numpy as np
import pytest

import chipbench_cells as cells
from benchmarks.chip import harness
from benchmarks.chip.reference import dml as ref_dml

TOL = 1e-4


@pytest.mark.parametrize("impl", ["ref", "interpret"])
@pytest.mark.parametrize("cell", cells.CELLS)
def test_program_agrees_with_the_reference(cell, impl, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", impl)
    cells.on_one_device(monkeypatch)
    seen = {}
    out = harness.run(cells.tiny(cell), 2 ** 31 + 7, 0.05, False,
                      on_chip=False,
                      after_build=lambda pop, fed:
                      seen.setdefault("impl", pop.impl))
    assert seen["impl"] == impl
    for name, check in out["checks"].items():
        assert check["value"] < TOL, (name, check)
    # a cell is correct only once its limits are set from chip readings
    assert out["correct"] is bool(cells.tiny(cell).limits)


def test_program_weights_are_the_reference_weights():
    import jax
    from benchmarks.chip import program
    cell = cells.tiny(cells.ONE_CHIP[0])
    pop, _ = program.build(cell.config, cell.traffic, 5, 1)
    make = program.weight_maker(cell.family, cell.config, cell.traffic,
                                pop.client_params)
    program.install_weights(pop, make, 2 ** 31 + 3)
    ref = ref_dml.Federation(cell.family, cell.config,
                             cell.traffic).init(2 ** 31 + 3)
    for c, want in enumerate(ref):
        got = cell.family.from_program(
            jax.tree.map(lambda t: t[c], pop.client_params))
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))


def test_rows_follow_the_seed():
    cell = cells.tiny(cells.ONE_CHIP[0])
    a = ref_dml.private_rows(cell.traffic, 512, 2 ** 31 + 1, 0)
    b = ref_dml.private_rows(cell.traffic, 512, 2 ** 31 + 1, 0)
    c = ref_dml.private_rows(cell.traffic, 512, 2 ** 31 + 2, 0)
    assert a.shape == (2, 4, 64)
    np.testing.assert_array_equal(a, b)
    assert (a != c).any()
