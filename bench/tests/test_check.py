"""The check that decides ``correct``, on each cell cut to a size the CPU
holds: the sound program passes it; the control (the plain reference at
the precision below the configuration's) and every fault the cell can have
fail it.  These drive the whole run but the look for a chip."""
import jax.numpy as jnp
import pytest

from conftest import run_small

SOLVE_CELLS = ("paper-cg", "resident-cg")


class Planted:
    """The system under test with one fault planted in its timed path."""

    def __init__(self, system, fault):
        self.system, self.fault = system, fault

    def solve(self, b, key, **kw):
        x, iters, mvms = self.system.solve(b, key, **kw)
        if self.fault == "state_unchanged":      # the solver never steps
            x = jnp.zeros_like(b)
        elif self.fault == "answer_altered":     # 0.1% off, where produced
            x = x * 1.001
        return x, iters, mvms

    def mvm(self, x, key):
        y = self.system.mvm(x, key)
        half = y.shape[1] // 2
        if self.fault == "half_batch":           # half computed, mean for rest
            mean = jnp.mean(y[:, :half], axis=1, keepdims=True)
            y = y.at[:, half:].set(jnp.broadcast_to(mean, y[:, half:].shape))
        elif self.fault == "state_unchanged":    # the input returned as is
            y = x
        elif self.fault == "answer_altered":     # one entry 0.1% off
            y = y.at[y.shape[0] // 3, half].multiply(1.001)
        return y

    def free(self):
        self.system.free()


def test_sound_program_is_correct(cell_name):
    result = run_small(cell_name)
    assert result["correct"], result["check"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["window_compiles"] == 0
    assert list(result)[-1] == "check"


def test_control_is_not_correct(cell_name):
    result = run_small(cell_name, control="high")
    assert not result["correct"], result["check"]


FAULTS = [(c, f) for c in SOLVE_CELLS
          for f in ("state_unchanged", "answer_altered")] + [
    ("resident-mvm-b64", f)
    for f in ("state_unchanged", "half_batch", "answer_altered")]


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_planted_fault_is_not_correct(cell, fault):
    result = run_small(cell, wrap=lambda s: Planted(s, fault))
    assert not result["correct"], (fault, result["check"])
