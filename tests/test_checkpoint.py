"""Checkpointing: atomicity, retention, async, exact resume, elastic."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager


@pytest.fixture
def state():
    return {"params": {"w": jnp.arange(12.0).reshape(3, 4)},
            "opt": [jnp.ones(3), {"v": jnp.zeros(2)}]}


def test_roundtrip_and_retention(tmp_path, state):
    cm = CheckpointManager(tmp_path, keep=2)
    for s in (10, 20, 30):
        cm.save(s, state, extra={"loader": {"step": s}}, blocking=True)
    assert cm.all_steps() == [20, 30]
    step, restored, extra = cm.restore()
    assert step == 30 and extra["loader"]["step"] == 30
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_async_save(tmp_path, state):
    cm = CheckpointManager(tmp_path, keep=3)
    cm.save(1, state)
    cm.wait()
    assert cm.latest_step() == 1


def test_atomicity_no_partial_dirs(tmp_path, state):
    cm = CheckpointManager(tmp_path, keep=3)
    cm.save(5, state, blocking=True)
    assert not list(tmp_path.glob("tmp.*"))
    # the completed step carries the terminal marker and no .tmp leftovers
    d = tmp_path / "step_0000000005"
    assert (d / "DONE").exists()
    assert not list(d.glob("*.tmp*"))


def test_half_written_step_is_ignored(tmp_path, state):
    """A step dir without the terminal DONE marker (crash mid-save, e.g. a
    non-atomic rename or a partial copy) is invisible to latest_step() and
    refused by restore() — recovery falls back to the previous step."""
    cm = CheckpointManager(tmp_path, keep=5)
    cm.save(1, state, blocking=True)
    cm.save(2, state, blocking=True)
    (tmp_path / "step_0000000002" / "DONE").unlink()  # simulate the crash
    assert cm.all_steps() == [1]
    assert cm.latest_step() == 1
    step, _, _ = cm.restore()
    assert step == 1
    with pytest.raises(FileNotFoundError, match="half-written"):
        cm.restore(2)
    # with no completed step at all, restore reports no checkpoints
    (tmp_path / "step_0000000001" / "DONE").unlink()
    with pytest.raises(FileNotFoundError):
        cm.restore()


def test_restore_specific_step(tmp_path, state):
    cm = CheckpointManager(tmp_path, keep=5)
    for s in (1, 2):
        st = jax.tree.map(lambda x: x + s, state)
        cm.save(s, st, blocking=True)
    step, restored, _ = cm.restore(1)
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["w"]),
        np.asarray(state["params"]["w"] + 1))


def test_elastic_restore_new_sharding(tmp_path, state):
    """Restore onto explicit (different) shardings — the elastic path."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh

    cm = CheckpointManager(tmp_path)
    cm.save(1, state, blocking=True)
    mesh = make_mesh((1,), ("data",))
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), state)
    _, restored, _ = cm.restore(shardings=sh)
    assert restored["params"]["w"].sharding == NamedSharding(mesh, P())
