import numpy as np
import pytest

from tpudist.elastic.checkpoint import (
    Checkpointer,
    latest_step,
    restore_pytree,
    save_pytree,
)


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "params": {"w": rng.random((4, 3), dtype=np.float32), "b": rng.random(3, dtype=np.float32)},
        "opt": [rng.random(2, dtype=np.float32), np.int32(7)],
    }


def test_roundtrip(tmp_path):
    tree = _tree()
    save_pytree(tmp_path / "ckpt.npz", tree, meta={"epoch": 3})
    restored, meta = restore_pytree(tmp_path / "ckpt.npz", _tree(seed=1))
    assert meta == {"epoch": 3}
    np.testing.assert_array_equal(restored["params"]["w"], tree["params"]["w"])
    np.testing.assert_array_equal(restored["opt"][1], tree["opt"][1])


def test_shape_mismatch_rejected(tmp_path):
    save_pytree(tmp_path / "c.npz", {"w": np.zeros((2, 2))})
    with pytest.raises(ValueError):
        restore_pytree(tmp_path / "c.npz", {"w": np.zeros((3, 3))})


def test_missing_leaf_rejected(tmp_path):
    save_pytree(tmp_path / "c.npz", {"w": np.zeros(2)})
    with pytest.raises(KeyError):
        restore_pytree(tmp_path / "c.npz", {"w": np.zeros(2), "extra": np.zeros(1)})


def test_checkpointer_latest_and_retention(tmp_path):
    ckpt = Checkpointer(tmp_path, keep=2)
    for step in (1, 5, 9):
        ckpt.save(step, _tree(step))
    assert latest_step(tmp_path) == 9
    step, tree, meta = ckpt.restore_latest(_tree())
    assert step == 9
    np.testing.assert_array_equal(tree["params"]["w"], _tree(9)["params"]["w"])
    # retention dropped step_1
    assert not (tmp_path / "step_1").exists()
    assert (tmp_path / "step_5").exists()


def test_checkpointer_ignores_uncommitted(tmp_path):
    ckpt = Checkpointer(tmp_path)
    ckpt.save(3, _tree())
    # a torn checkpoint: directory exists, no COMMITTED marker
    (tmp_path / "step_7").mkdir()
    (tmp_path / "step_7" / "state.npz").write_bytes(b"garbage")
    assert latest_step(tmp_path) == 3


def test_async_save(tmp_path):
    ckpt = Checkpointer(tmp_path, async_save=True)
    tree = _tree()
    ckpt.save(1, tree)
    tree["params"]["w"][:] = -1  # mutate after save returns: must not affect checkpoint
    ckpt.wait()
    _, restored, _ = ckpt.restore_latest(_tree(1))
    assert not np.any(restored["params"]["w"] == -1)


def test_restore_latest_empty(tmp_path):
    assert Checkpointer(tmp_path / "nope").restore_latest(_tree()) is None


def test_invalid_layout_rejected(tmp_path):
    with pytest.raises(ValueError, match="layout"):
        Checkpointer(tmp_path, layout="nested")


def test_flat_layout_roundtrip_and_replace(tmp_path):
    """layout='flat': the target IS one .npz file every save replaces —
    the Trainer's rolling snapshot contract on the shared save path."""
    ckpt = Checkpointer(tmp_path / "snap.npz", layout="flat")
    ckpt.save(4, _tree(), meta={"step": 4})
    assert (tmp_path / "snap.npz").exists()
    assert not any(p.name.startswith("step_") for p in tmp_path.iterdir())
    step, tree, meta = ckpt.restore_latest(_tree(1))
    assert step == 4 and meta["step"] == 4
    np.testing.assert_array_equal(tree["params"]["w"], _tree()["params"]["w"])
    ckpt.save(9, _tree(9), meta={"step": 9})
    step, tree, _ = ckpt.restore_latest(_tree(1))
    assert step == 9
    np.testing.assert_array_equal(tree["params"]["w"], _tree(9)["params"]["w"])


def test_flat_layout_empty(tmp_path):
    none = Checkpointer(tmp_path / "no.npz", layout="flat")
    assert none.restore_latest(_tree()) is None


def test_async_save_device_tree_survives_donation(tmp_path):
    """Async saves stage an ON-DEVICE copy before returning, so a
    donating dispatch immediately after save() cannot clobber the
    checkpoint (the donation-vs-async-fetch rule, docs/DESIGN.md), and
    device-scalar meta values resolve to JSON on the writer thread."""
    import jax
    import jax.numpy as jnp

    x = jnp.arange(1024.0, dtype=jnp.float32)
    want = np.asarray(x)
    ckpt = Checkpointer(tmp_path, async_save=True)
    ckpt.save(1, {"x": x}, meta={"step": jnp.int32(1), "tag": "e2e"})
    bump = jax.jit(lambda v: v + 1.0, donate_argnums=0)
    x = bump(x)            # donates the buffer save() was handed
    float(x[0])            # force the donating dispatch to complete
    ckpt.wait()
    _, restored, meta = ckpt.restore_latest({"x": want})
    np.testing.assert_array_equal(restored["x"], want)
    assert meta["step"] == 1 and meta["tag"] == "e2e"


def test_async_meta_scalar_survives_deletion(tmp_path, monkeypatch):
    """Regression for the meta donation race: the caller's next donating
    dispatch deletes the live device scalar passed in ``meta`` BEFORE the
    writer thread resolves it.  Meta must be staged (on-device copy) at
    save() initiation; a bare reference would resolve to garbage or kill
    the writer.  The writer is gated so the deletion deterministically
    happens first."""
    import threading

    import jax.numpy as jnp

    from tpudist.elastic import checkpoint as ck

    gate = threading.Event()
    real = ck.tree_to_numpy

    def gated(tree):
        gate.wait(timeout=10)
        return real(tree)

    monkeypatch.setattr(ck, "tree_to_numpy", gated)
    step = jnp.int32(7)
    ckpt = Checkpointer(tmp_path / "s.npz", async_save=True, layout="flat")
    ckpt.save(7, {"x": jnp.zeros(8)}, meta={"step": step, "epochs_run": 3})
    step.delete()  # what a donating dispatch does to the live buffer
    gate.set()
    ckpt.wait()    # raises if the writer died on the deleted array
    _, _, meta = ckpt.restore_latest({"x": np.zeros(8, np.float32)})
    assert meta["step"] == 7 and meta["epochs_run"] == 3


def test_async_save_failure_raises_from_wait(tmp_path, monkeypatch):
    """A failed background write must surface, not be swallowed: wait()
    re-raises the captured exception (once), so callers joining before
    declaring the snapshot durable see the same failure the sync path
    would have raised."""
    from tpudist.elastic import checkpoint as ck

    def boom(*a, **k):
        raise OSError("disk full")

    monkeypatch.setattr(ck, "save_pytree", boom)
    ckpt = Checkpointer(tmp_path / "s.npz", async_save=True, layout="flat")
    ckpt.save(0, _tree())
    with pytest.raises(OSError, match="disk full"):
        ckpt.wait()
    ckpt.wait()  # raised once, then cleared


def test_async_save_failure_raises_from_next_save(tmp_path, monkeypatch):
    from tpudist.elastic import checkpoint as ck

    calls = {"n": 0}
    real = ck.save_pytree

    def flaky(*a, **k):
        calls["n"] += 1
        if calls["n"] == 1:
            raise OSError("boom")
        return real(*a, **k)

    monkeypatch.setattr(ck, "save_pytree", flaky)
    ckpt = Checkpointer(tmp_path / "s.npz", async_save=True, layout="flat")
    ckpt.save(0, _tree())
    with pytest.raises(OSError, match="boom"):
        ckpt.save(1, _tree(1))


def test_async_flat_save_records_blocked_time(tmp_path):
    from tpudist import obs

    before = obs.snapshot()["histograms"].get(
        "ckpt/save_blocked", {}).get("count", 0)
    ckpt = Checkpointer(tmp_path / "s.npz", async_save=True, layout="flat")
    ckpt.save(0, _tree())
    ckpt.wait()
    assert obs.snapshot()["histograms"]["ckpt/save_blocked"]["count"] > before


def test_async_flat_save_equals_sync_save(tmp_path):
    """The async path only moves WHEN the bytes are written: what it
    restores equals what the synchronous save of the same device tree
    restores, leaf for leaf."""
    import jax

    rng = np.random.default_rng(0)
    leaf = rng.normal(size=(64, 64)).astype(np.float32)
    tree = {f"w{i}": jax.device_put(leaf + i) for i in range(4)}
    sync_ck = Checkpointer(tmp_path / "sync.npz", async_save=False,
                           layout="flat")
    async_ck = Checkpointer(tmp_path / "async.npz", async_save=True,
                            layout="flat")
    sync_ck.save(0, tree, meta={"step": 0})
    async_ck.save(0, tree, meta={"step": 0})
    async_ck.wait()
    a, _ = restore_pytree(tmp_path / "async.npz", tree)
    s, _ = restore_pytree(tmp_path / "sync.npz", tree)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(s[k]))
        np.testing.assert_array_equal(np.asarray(a[k]), leaf + int(k[1:]))
