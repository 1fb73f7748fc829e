"""``telemetry/op_scopes.py::live_bytes`` on real compiled steps: a tiny
model of each family through ``build_train_step`` on the CPU, the state
donated as a trainer donates it.  The reading is held to XLA's own figure,
its owners add up, recomputation leaves fewer residuals at the peak, and a
reading that is off XLA's figure hands out no split.  (The walk's rules on
hand-written schedules are ``tests/test_op_scopes.py``; a file of its own so
that the compiles run beside that file's, not after them.)"""

import functools

import pytest
from test_op_scopes import _lowered

from elasticdl_tpu.telemetry import op_scopes

# one of each construct the walk reads: an unrolled stack with and without
# recomputation, a scanned and looped stack (``while``), the expert ladder's
# branches (``conditional``) under recomputed layers, a convolutional model
# with batch statistics among its buffers
COMPILED = (
    "gpt2_block", "gpt2_block_remat", "looped_stack",
    "window_and_full_attention", "resnet_first_stage",
)


@functools.lru_cache(maxsize=None)
def _donating(family):
    """The family's step compiled as a trainer runs it: the state donated."""
    return _lowered(family, donate=True)[1].compile()


@pytest.mark.parametrize("family", COMPILED)
def test_the_reading_of_a_compiled_step_is_held_to_xlas_figure(family):
    compiled = _donating(family)
    read = op_scopes.live_bytes(compiled)
    assert op_scopes.live_bytes(compiled) is read  # made once a program
    # the CPU's peak names no temporaries: the total is what it is held to
    assert read["held_to"] == "total"
    assert 0.9 <= read["ratio"] <= 1.1, read["ratio"]
    assert read["xla"]["total"] == (
        read["xla"]["argument"] + read["xla"]["temp"]
        + read["xla"]["output"] - read["xla"]["alias"]
    )
    # the owners' bytes add up to the total, the state's leaves are all
    # donated, and the arguments are the state's and the batch's
    assert sum(size for *_, size in read["live"]) == read["peak_bytes"]
    assert read["undonated"] == []
    owners = {owner for owner, _, role, _ in read["live"] if role == "argument"}
    assert {"params", "batch"} <= owners
    assert owners <= {
        "params", "opt_state", "step", "batch", "router_stats", "batch_stats",
        "loss_parts", "block_plan", "exits",
    }, owners
    assert read["phase"] in ("forward", "backward", "recompute", "optimizer")
    assert read["instruction"] in op_scopes.scope_map(compiled)


def test_recomputation_leaves_fewer_residuals_at_the_peak():
    def residuals(read):
        return sum(size for _, _, role, size in read["live"] if role == "residual")

    plain = op_scopes.live_bytes(_donating("gpt2_block"))
    remat = op_scopes.live_bytes(_donating("gpt2_block_remat"))
    assert plain["phase"] in ("backward", "optimizer")
    assert remat["phase"] in ("backward", "optimizer")
    assert 0 < residuals(remat) < residuals(plain)
    # what a residual is: made by the forward pass
    assert {phase for _, phase, role, _ in plain["live"] if role == "residual"} == {
        "forward"
    }


def test_a_reading_off_xlas_figure_hands_out_no_split():
    compiled = _lowered("gpt2_block")[1].compile()  # nothing donated

    class Doubled:
        """The same text under an XLA that says it holds twice as much."""

        def as_text(self):
            return compiled.as_text()

        def memory_analysis(self):
            real = compiled.memory_analysis()
            return type("A", (), {
                name: 2 * getattr(real, name)
                for name in dir(real) if name.endswith("bytes")
            })()

    read = op_scopes.live_bytes(Doubled())
    assert read["ratio"] < 0.9 and read["live"] is None and read["largest"] is None
    assert read["peak_bytes"] and read["instruction"]  # where, and how far off
    assert "outside" in op_scopes.memory_table(read)
