"""Checkpointing: atomic roundtrip, async, resume, elastic re-shard plan,
straggler/failure policy."""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.ckpt.checkpoint import CheckpointManager
from repro.ckpt.straggler import HeartbeatMonitor, plan_recovery
from repro.launch.mesh import make_mesh


def tree():
    return {"w": jnp.arange(12.0).reshape(3, 4),
            "opt": {"mu": jnp.ones((5,)), "count": jnp.asarray(3)},
            "none": None}


def test_roundtrip(tmp_path):
    mgr = CheckpointManager(tmp_path)
    state = tree()
    mgr.save(7, state, metadata={"next_step": 7})
    out, meta = mgr.restore(state)
    assert meta["next_step"] == 7
    np.testing.assert_array_equal(out["w"], np.asarray(state["w"]))
    np.testing.assert_array_equal(out["opt"]["mu"],
                                  np.asarray(state["opt"]["mu"]))
    assert out["none"] is None


def test_async_save_and_latest(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    for s in [1, 2, 3]:
        mgr.save_async(s, tree())
    mgr.wait()
    assert mgr.latest_step() == 3
    assert mgr.all_steps() == [2, 3]  # keep=2 garbage-collects step 1


def test_torn_save_ignored(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, tree())
    # simulate a crash mid-save: stray tmp dir
    (tmp_path / "step_00000002.tmp").mkdir()
    (tmp_path / "step_00000002.tmp" / "junk.npy").write_bytes(b"xx")
    assert mgr.latest_step() == 1
    out, _ = mgr.restore(tree())
    np.testing.assert_array_equal(out["w"], np.arange(12.0).reshape(3, 4))


def test_resave_same_step(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save(5, tree())
    mgr.save(5, tree())  # periodic + final save collision must not raise
    assert mgr.latest_step() == 5


def test_restore_with_target_sharding(tmp_path):
    """Elastic restore: leaves are placed with the *target* sharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = make_mesh((1,), ("data",))
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, tree())
    sh = {"w": NamedSharding(mesh, P("data", None)),
          "opt": {"mu": NamedSharding(mesh, P()), "count": None},
          "none": None}
    out, _ = mgr.restore(tree(), shardings=sh)
    assert isinstance(out["w"], jax.Array)
    assert out["w"].sharding.spec == P("data", None)


# --- straggler / recovery ---------------------------------------------------

def test_heartbeat_failure_detection():
    mon = HeartbeatMonitor(4, timeout_s=10)
    for h in range(4):
        mon.beat(h, step=1, now=100.0, step_s=1.0)
    mon.beat(0, step=2, now=105.0, step_s=1.0)
    assert mon.failed(now=112.0) == [1, 2, 3]
    assert mon.failed(now=106.0) == []


def test_straggler_detection():
    mon = HeartbeatMonitor(4, straggler_factor=2.0)
    times = [1.0, 1.1, 0.9, 5.0]
    for h, t in enumerate(times):
        for s in range(5):
            mon.beat(h, step=s, now=float(s), step_s=t)
    assert mon.stragglers() == [3]
    assert 3 not in mon.healthy(now=4.0)


@settings(max_examples=25, deadline=None)
@given(n_fail=st.integers(0, 48), model_axis=st.sampled_from([8, 16]))
def test_recovery_plan_valid(n_fail, model_axis):
    hosts_total = 64
    chips = 4
    surviving = list(range(hosts_total - n_fail))
    if len(surviving) * chips < model_axis:
        return
    plan = plan_recovery(surviving, hosts_total=hosts_total,
                         old_mesh=(hosts_total * chips // model_axis,
                                   model_axis),
                         model_axis=model_axis, chips_per_host=chips)
    data, model = plan.mesh_shape
    assert model == model_axis
    assert data * model <= len(surviving) * chips
    old_data = hosts_total * chips // model_axis
    assert old_data % data == 0
    assert plan.accum_scale == old_data // data  # global batch preserved
    assert set(plan.hosts) <= set(surviving)
