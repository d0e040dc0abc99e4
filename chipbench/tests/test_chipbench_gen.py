"""The vectorised traffic copies keep the program generators' semantics."""
import numpy as np
import pytest

from chipbench import gen
from repro.data.asmgen import spec_programs
from repro.data.perfmodel import INORDER_CPU, trace_cpi
from repro.data.trace import INTERVAL_INSTRS, block_table, trace_program

PROGRAMS = spec_programs("int")


@pytest.mark.parametrize("which", [0, 2, 9])
def test_interval_copy_keeps_schedule_block_sets_and_budget(which):
    prog = PROGRAMS[which]
    want = trace_program(prog, 120, seed=5)
    got = gen.trace(prog, 120, seed=2**40 + 5).intervals(prog.name)
    max_instrs = max(b.num_instrs for b in prog.unique_blocks)
    n_blocks = len(prog.unique_blocks)
    for a, b in zip(want, got):
        assert a.phase_id == b.phase_id
        assert set(a.counts) == set(b.counts)
        # each block's count is floored: the budget is 10 M less at most
        # one execution of every block
        assert INTERVAL_INSTRS - n_blocks * max_instrs <= b.num_instrs
        assert b.num_instrs <= INTERVAL_INSTRS
        assert b.num_instrs == sum(c * next(
            x.num_instrs for x in prog.unique_blocks if x.bid == bid)
            for bid, c in b.counts.items())


def test_phase_schedule_matches_trace_program():
    prog = PROGRAMS[4]
    want = [iv.phase_id for iv in trace_program(prog, 200, seed=0)]
    assert gen.phase_schedule(prog, 200).tolist() == want


def test_same_seed_same_traffic_other_seed_other_jitter():
    prog = PROGRAMS[1]
    a, b = gen.trace(prog, 50, 3), gen.trace(prog, 50, 3)
    c = gen.trace(prog, 50, 4)
    assert np.array_equal(a.counts, b.counts)
    assert not np.array_equal(a.counts, c.counts)


def test_inorder_cpi_copy_equals_the_program_model():
    tr = gen.trace(PROGRAMS[3], 80, seed=11)
    ivs = tr.intervals("p")
    want = trace_cpi(ivs, block_table(PROGRAMS), INORDER_CPU)
    np.testing.assert_allclose(gen.inorder_cpi(tr), want, rtol=1e-12)
