"""Fault-tolerance + compression tests: failure injection -> restore,
elastic re-mesh decision, straggler power-shift, int8 error feedback."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.core import PowerCappedDevice, TPU_V5E, WorkloadProfile
from repro.core.powershift import ClusterNode
from repro.runtime.compress import (compress_residual, dequantize_int8,
                                    init_error_state, quantize_int8)
from repro.control import EventBus, NodeDerated
from repro.runtime.fault import (ServingSupervisor, Supervisor,
                                 SupervisorConfig)


# --------------------------------------------------------------------------
# supervisor
# --------------------------------------------------------------------------
def _trainer(tmp_path, inject=None, n_steps=12, elastic=True):
    """A toy counting 'training' job under supervision."""
    ckpt = CheckpointManager(tmp_path, keep=2)
    state0 = {"x": jnp.zeros(())}
    ckpt.save(state0, 0)

    def step_fn(state, batch):
        return {"x": state["x"] + batch}, {"loss": float(10.0 - state["x"])}

    sup = Supervisor(
        SupervisorConfig(checkpoint_every=4, elastic=elastic),
        save_fn=lambda s, i: ckpt.save(s, i),
        restore_fn=lambda: (ckpt.restore(state0), ckpt.latest_step() or 0))
    sup.register("node-0")
    sup.register("node-1")
    batches = [jnp.asarray(1.0)] * n_steps
    state, report = sup.run(step_fn, state0, batches,
                            inject_failure_at=inject or {})
    return state, report


def test_supervisor_clean_run(tmp_path):
    state, report = _trainer(tmp_path)
    assert report["final_step"] == 12
    assert report["restarts"] == 0
    assert float(state["x"]) == 12.0


def test_supervisor_recovers_from_failure(tmp_path):
    state, report = _trainer(tmp_path, inject={6: "node-1"})
    assert report["restarts"] == 1
    events = [e["event"] for e in report["events"]]
    assert "recovery" in events
    # resumed from the step-4 checkpoint: at most (failure_step - ckpt_step)
    # + 1 batch of work lost, training continued past the failure point
    assert report["final_step"] > 6
    assert float(state["x"]) > 4.0


def test_supervisor_elastic_remesh_decision(tmp_path):
    ckpt = CheckpointManager(tmp_path)
    ckpt.save({"x": jnp.zeros(())}, 0)
    sup = Supervisor(SupervisorConfig(elastic=True),
                     save_fn=lambda s, i: ckpt.save(s, i),
                     restore_fn=lambda: ({"x": jnp.zeros(())}, 0))
    for i in range(8):
        sup.register(f"n{i}")
    sup.workers["n3"].alive = False
    decision = sup.handle_failure(["n3"])
    assert decision["action"] == "remesh"
    assert decision["new_dp"] == 4          # 7 alive -> largest pow2 = 4


def test_supervisor_abort_after_budget(tmp_path):
    ckpt = CheckpointManager(tmp_path)
    ckpt.save({"x": jnp.zeros(())}, 0)
    sup = Supervisor(SupervisorConfig(max_restarts=1, elastic=False),
                     save_fn=lambda s, i: None,
                     restore_fn=lambda: ({"x": jnp.zeros(())}, 0))
    sup.register("n0")
    sup.handle_failure(["n0"])
    assert sup.handle_failure(["n0"])["action"] == "abort"


def test_supervisor_failure_detected_via_liveness(tmp_path):
    """Injection stalls the node's heartbeat instead of flagging it dead
    directly — recovery proves check_liveness is wired into run()."""
    state, report = _trainer(tmp_path, inject={6: "node-1"})
    events = [e["event"] for e in report["events"]]
    assert "node_dead" in events                  # liveness saw the silence
    assert events.index("node_dead") < events.index("recovery")


def test_supervisor_restores_exactly_once_per_failure(tmp_path):
    """handle_failure restores the checkpoint; run() must reuse that state
    via take_restored instead of paying (and counting) a second restore."""
    from repro.checkpoint import CheckpointManager
    ckpt = CheckpointManager(tmp_path, keep=2)
    state0 = {"x": jnp.zeros(())}
    ckpt.save(state0, 0)
    n_restores = {"n": 0}

    def restore_fn():
        n_restores["n"] += 1
        return ckpt.restore(state0), ckpt.latest_step() or 0

    sup = Supervisor(SupervisorConfig(checkpoint_every=4),
                     save_fn=lambda s, i: ckpt.save(s, i),
                     restore_fn=restore_fn)
    sup.register("node-0")
    sup.register("node-1")
    step_fn = lambda s, b: ({"x": s["x"] + b}, {"loss": 0.0})
    _, report = sup.run(step_fn, state0, [jnp.asarray(1.0)] * 12,
                        inject_failure_at={6: "node-1"})
    assert report["restarts"] == 1
    assert n_restores["n"] == 1                   # once, not once-per-caller


def test_supervisor_heartbeat_auto_registers_unknown_node():
    sup = Supervisor(SupervisorConfig(), save_fn=lambda s, i: None,
                     restore_fn=lambda: (None, 0))
    sup.heartbeat("joiner", step=3, latency_s=0.5)   # elastic scale-up
    assert "joiner" in sup.workers and sup.workers["joiner"].step == 3
    assert any(e["event"] == "auto_register" for e in sup.events)


def test_serving_supervisor_publishes_derate():
    """Chunk-wall inflation becomes a NodeDerated on the control bus: the
    serving half of the FROST straggler loop."""
    bus = EventBus()
    derated = bus.tap(NodeDerated)
    sup = ServingSupervisor(bus=bus, node_id="serve-0",
                            baseline_wall_s=1.0, ewma=0.0)
    sup.on_heartbeat(4, 1.0)                      # healthy: no publish
    assert not derated
    for step in range(8, 24, 4):
        sup.on_heartbeat(step, 2.0)               # chunks run 2x slow
    assert derated and derated[-1].derate == pytest.approx(0.5)
    assert sup.workers["serve-0"].derate == pytest.approx(0.5)
    n = len(derated)
    sup.on_heartbeat(24, 2.0)                     # unchanged: delta-gated
    assert len(derated) == n


def test_serving_supervisor_tick_fires_on_dead():
    t = {"now": 0.0}
    dead_nodes = []
    sup = ServingSupervisor(SupervisorConfig(heartbeat_timeout_s=5.0),
                            on_dead=dead_nodes.append,
                            clock=lambda: t["now"])
    sup.on_heartbeat(0, 0.01)
    t["now"] = 3.0
    assert sup.tick() == [] and not dead_nodes    # within the window
    t["now"] = 10.0                               # engine went silent
    assert sup.tick() == ["serve-0"]
    assert dead_nodes == ["serve-0"]


def test_straggler_detection_and_rebalance(tmp_path):
    sup = Supervisor(SupervisorConfig(straggler_threshold=1.2),
                     save_fn=lambda s, i: None,
                     restore_fn=lambda: (None, 0))
    sup.register("fast0"); sup.register("fast1"); sup.register("slow")
    sup.heartbeat("fast0", 1, 1.0)
    sup.heartbeat("fast1", 1, 1.05)
    sup.heartbeat("slow", 1, 1.6)
    stragglers, lat = sup.straggler_report()
    assert stragglers == ["slow"]
    # FROST power-shift: derated node must receive a higher cap
    wl = WorkloadProfile(name="w", flops_per_step=5e12, hbm_bytes_per_step=2e9)
    nodes = [ClusterNode("fast0", PowerCappedDevice(TPU_V5E), wl),
             ClusterNode("slow", PowerCappedDevice(TPU_V5E, derate=0.75), wl)]
    plan = sup.rebalance_power(nodes, budget_w=1.8 * TPU_V5E.tdp_w)
    caps = {a.node_id: a.cap for a in plan.allocations}
    assert caps["slow"] >= caps["fast0"]


# --------------------------------------------------------------------------
# compression
# --------------------------------------------------------------------------
def test_quantize_roundtrip_bounds():
    x = jnp.asarray([-3.0, -0.1, 0.0, 0.5, 2.9])
    q, scale = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, scale) - x))
    assert err.max() <= float(scale) / 2 + 1e-7


def test_error_feedback_telescopes():
    """Sum of dequantized values + final residual == sum of true values —
    the telescoping identity that preserves SGD convergence."""
    rng = np.random.default_rng(0)
    xs = [jnp.asarray(rng.normal(size=16), jnp.float32) for _ in range(50)]
    e = jnp.zeros(16)
    total_sent = jnp.zeros(16)
    for x in xs:
        q, scale, e = compress_residual(x + e)
        total_sent = total_sent + dequantize_int8(q, scale)
    true_total = sum(np.asarray(x) for x in xs)
    # residual e is the only unsent mass
    np.testing.assert_allclose(np.asarray(total_sent + e), true_total,
                               rtol=1e-5, atol=1e-5)


def test_compressed_psum_single_device_mesh():
    """compressed_psum over a size-1 axis == identity (mean of one)."""
    mesh = jax.make_mesh((1,), ("pod",))
    from jax.sharding import PartitionSpec as P
    from repro.runtime.compress import compressed_psum

    g = {"w": jnp.asarray([0.5, -1.5, 2.0])}
    e = init_error_state(g)

    def inner(g, e):
        return compressed_psum(g, "pod", e)

    out, err = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(), g),) * 2,
        out_specs=(jax.tree.map(lambda _: P(), g),) * 2,
        check_vma=False)(g, e)
    np.testing.assert_allclose(np.asarray(out["w"]), [0.5, -1.5, 2.0],
                               atol=0.02)
    # error feedback captured the quantization residual
    np.testing.assert_allclose(np.asarray(out["w"] + err["w"]),
                               [0.5, -1.5, 2.0], atol=1e-6)
