"""The control, the reference computed at the precision below the
configuration's (``reference.CONTROL``: bfloat16 for float32 at the default
matmul precision) and put in the program's place, comes out not correct
under the real cells' limits, while the program on the same batches comes
out correct.  (On the chip the control is read at the cells' own size by
``bench/calibrate.py``.)"""

import pytest

import tinycell
from bench import compare, dataset, harness, reference


@pytest.mark.parametrize("model", ["rgcn", "hgt"])
@pytest.mark.parametrize("seed", [5, 2**31 + 17])
def test_control_fails_where_the_program_passes(tmp_path, model, seed):
    root = tinycell.root_with_limits(tmp_path, model)
    cell = harness.open_cell(f"tiny-{model}.frozen", tinycell.bm(model), root)
    ds = dataset.load(cell.config["dataset"])
    matmul = cell.config["precision"]["matmul"]
    setup = reference.make_setup(ds, cell.heta, matmul)
    with harness.matmul_precision(cell):
        sess = harness.start_session(cell, dataset.to_hetgraph(ds), seed)
        warm = harness.warm_up(sess, setup)
    harness.release(sess)
    ref = harness.Reference(setup, ds, warm, seed)
    program = ref.program()
    assert compare.judge(program, cell.limits), program
    ctrl = ref.stand_in(reference.CONTROL[matmul])
    assert not compare.judge(ctrl, cell.limits), ctrl
