"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        --smoke --steps 20 --frost

Wires together: config registry -> data pipeline -> sharded train step ->
FROST control plane (batch profile warm-starts an online profiler that
keeps retuning the cap from streamed step telemetry) -> FT supervisor
(heartbeats, checkpoint/restart, straggler power-shifting) -> telemetry
ledger.  On this CPU container use --smoke (reduced configs); the full
configs are exercised through the dry-run.

Every train step publishes ``StepDone`` on the control-plane bus and reads
the enforcement backend before the next step, so cap commands issued by the
online profiler (or a cluster coordinator) take effect mid-run — the
paper's Fig 1 loop, not a one-shot offline probe.

Real-TPU deployments additionally want the XLA latency-hiding scheduler:
    LIBTPU_INIT_ARGS="--xla_tpu_enable_async_collective_fusion=true"
    XLA_FLAGS="--xla_tpu_enable_latency_hiding_scheduler=true"
(recorded here, inert on CPU).
"""
from __future__ import annotations

import argparse
import tempfile
import time

import jax

from repro.configs import get_arch
from repro.control import CapApplied, EventBus, StepDone
from repro.control.online import OnlineCapProfiler
from repro.core import (CapProfiler, PowerCappedDevice, QoSPolicy,
                        WorkloadProfile, device_spec)
from repro.core.profiler import RecordingBackend
from repro.checkpoint import CheckpointManager
from repro.data import DataConfig, TokenBatches
from repro.launch.mesh import make_host_mesh
from repro.models.config import ModelConfig
from repro.optim import OptimizerConfig
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.fault import Supervisor, SupervisorConfig
from repro.runtime.sharding import build_rules
from repro.runtime.steps import StepConfig, init_train_state, jit_train_step
from repro.telemetry.meters import AnalyticDeviceMeter, CpuProcessMeter, DramMeter
from repro.telemetry.sampler import PowerSampler


def profile_cap_for_step(cfg: ModelConfig, flops: float, bytes_hbm: float,
                         coll: float, policy: QoSPolicy, *,
                         bus=None, backend=None):
    """FROST batch pass: given the compiled step's roofline terms, pick the
    cap.  Returns (decision, workload, device) so the online profiler can
    warm-start from the same artefacts."""
    wl = WorkloadProfile(name=cfg.name, flops_per_step=flops,
                         hbm_bytes_per_step=bytes_hbm,
                         collective_bytes_per_step=coll,
                         samples_per_step=1)
    dev = PowerCappedDevice(device_spec(jax.devices()[0]))

    class _W:                                   # Workload protocol adapter
        def probe(self, cap, duration_s):
            return dev.probe(wl, cap, duration_s)

    prof = CapProfiler(_W(), policy=policy, probe_seconds=30.0,
                       bus=bus, backend=backend, node_id="node-0")
    return prof.run(), wl, dev


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory (default: a fresh temp dir)")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--frost", action="store_true",
                    help="run the FROST control plane (batch profile warm-"
                         "starts an online retuner over the step stream)")
    ap.add_argument("--edp-exponent", type=float, default=2.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    spec = get_arch(args.arch)
    cfg = spec.smoke if args.smoke else spec.config
    print(f"[train] arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"family={cfg.family}")

    step_cfg = StepConfig(
        n_micro=args.n_micro, remat="none",
        optimizer=OptimizerConfig(learning_rate=args.lr,
                                  warmup_steps=max(2, args.steps // 10),
                                  total_steps=args.steps))

    mesh = make_host_mesh()
    rules = build_rules(cfg, mesh) if mesh.devices.size > 1 else None

    key = jax.random.PRNGKey(args.seed)
    state, axes = init_train_state(key, cfg, step_cfg)
    data = TokenBatches(DataConfig(
        seed=args.seed, vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch, n_codebooks=cfg.n_codebooks))
    train_step, state, place_batch = jit_train_step(
        cfg, step_cfg, rules, state, axes, data.batch(0), donate=True)
    batch_at = lambda i: place_batch(data.batch(i))
    if rules is not None:
        print(f"[train] mesh {dict(mesh.shape)} over "
              f"{len(mesh.devices.flat)} devices")

    # -- FROST control plane (paper Sec III-C + Fig 1 loop) --------------------
    bus = EventBus()
    backend = RecordingBackend()
    cap_log = bus.tap(CapApplied)        # lossless cap-command accounting
    frost_wl = frost_dev = online = gpu_meter = None
    if args.frost:
        policy = QoSPolicy(policy_id=f"train-ed{args.edp_exponent:g}p",
                           edp_exponent=args.edp_exponent)
        # derive roofline terms from one compiled step
        from repro.launch import hloparse
        lowered = train_step.lower(state, batch_at(0))
        compiled = lowered.compile()
        h = hloparse.analyze(compiled.as_text())
        ca = compiled.cost_analysis()
        decision, frost_wl, frost_dev = profile_cap_for_step(
            cfg, h["dot_flops"], float(ca.get("bytes accessed", 0.0)),
            h["collective_bytes"], policy, bus=bus, backend=backend)
        print(f"[frost] selected cap = {decision.cap:.0%} "
              f"(pred. energy saving {decision.predicted_energy_saving:+.1%}, "
              f"delay {decision.predicted_delay_increase:+.1%}, "
              f"fit rmse {decision.fit.rel_rmse:.3%})")
        # warm-start the online retuner: no further dedicated probe windows —
        # refreshes are amortised across live train steps
        gpu_meter = AnalyticDeviceMeter(frost_dev, frost_wl, cap=decision.cap)
        gpu_meter.set_workload(frost_wl, busy=True)
        online = OnlineCapProfiler(bus, backend, policy=policy,
                                   node_id="node-0", model_id=cfg.name,
                                   steps_per_probe=2, hold_steps=16,
                                   warm_start=decision)

    meters = {"cpu": CpuProcessMeter(), "dram": DramMeter(4, 16)}
    if gpu_meter is not None:
        meters["gpu"] = gpu_meter
    sampler = PowerSampler(meters, rate_hz=0.1, bus=bus, node_id="node-0")

    # -- supervised run ----------------------------------------------------------
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="repro_ckpt_")
    ckpt = CheckpointManager(ckpt_dir, keep=2, save_async=True)
    ckpt.save(state, 0)                    # recovery floor before step 1
    sup = Supervisor(
        SupervisorConfig(checkpoint_every=args.ckpt_every),
        save_fn=lambda s, i: ckpt.save(s, i),
        restore_fn=lambda: (ckpt.restore(state), ckpt.latest_step() or 0))
    sup.register("node-0")

    step_no = {"i": 0}

    def instrumented_step(state, batch):
        """The step loop as a control-plane producer: run the jitted step,
        honour whatever cap is currently enforced, publish StepDone."""
        state, metrics = train_step(state, batch)
        cap = backend.current_cap()            # cap commands land mid-run
        if frost_dev is not None:
            gpu_meter.set_cap(cap)
            est = frost_dev.estimate(frost_wl, cap)
            duration_s, energy_j = est.step_time_s, est.energy_j
        else:
            duration_s, energy_j = 0.0, 0.0
        i = step_no["i"] = step_no["i"] + 1
        if duration_s > 0:
            # samples must match the profile workload's samples_per_step (1):
            # the online drift check compares time/SAMPLE against the batch
            # profile's expectation, so mixed units read as huge fake drift.
            bus.publish(StepDone(node_id="node-0", step=i,
                                 duration_s=duration_s,
                                 samples=frost_wl.samples_per_step,
                                 energy_j=energy_j, model_id=cfg.name))
        return state, metrics

    batches = (batch_at(i) for i in range(args.steps))
    t0 = time.time()
    with sampler:
        state, report = sup.run(instrumented_step, state, batches)
    dt = time.time() - t0
    losses = [h["loss"] for h in report["history"]]
    print(f"[train] {report['final_step']} steps in {dt:.1f}s "
          f"({dt/max(report['final_step'],1):.3f}s/step); "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f}")
    if online is not None:
        print(f"[frost-ctrl] {len(cap_log)} cap commands over the run; "
              f"online refits={online.n_refits} "
              f"cap now {backend.current_cap():.0%}")
        online.close()
    ckpt.wait()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
