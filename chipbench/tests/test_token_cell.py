"""A token-input cell from new files alone: the paper's CharLSTM
(``tests/data/charlstm.{json,py}``) on the ``tokens`` input kind, driven
through the unchanged harness on the CPU.  The program passes against the
reference; the bfloat16 control and planted faults do not."""
import pytest

from chipbench.tests.conftest import TOKEN_MIX, run_tiny
from chipbench.tests.test_correct import FAULTS

pytestmark = pytest.mark.usefixtures("cpu_only", "charlstm_on_adam")
CELL = "charlstm-tok"


def test_sound_program_is_correct(token_bench):
    specs = []
    res = run_tiny(token_bench, CELL, patch=lambda prog: specs.append(prog.spec))
    assert res["correct"], res["checks"]
    assert specs[0].seq_len == TOKEN_MIX["seq_len"] != 64  # 64: RunSpec's default
    assert res["metrics"]["samples_per_s"]["value"] > 0


def test_control_in_bfloat16_is_not_correct(token_bench):
    import jax
    import jax.numpy as jnp

    from chipbench import harness
    from chipbench.check import compare

    root, _ = token_bench
    c = harness.load_cell(CELL, root)
    ref = harness.reference_evidence(c, 11, jax.devices()[0])
    control = harness.reference_evidence(c, 11, jax.devices()[0], jnp.bfloat16)
    values, _ = compare.numbers(control, ref)
    ok, rows = compare.verdict(values, c.workload["limits"])
    assert not ok, rows


@pytest.mark.parametrize("fault", ["no_exchange", "unchanged", "altered"])
def test_planted_fault_is_not_correct(token_bench, fault):
    res = run_tiny(token_bench, CELL, patch=FAULTS[fault])
    assert not res["correct"], res["checks"]
