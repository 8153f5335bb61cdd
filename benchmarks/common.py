"""Shared benchmark infrastructure.

Per CNN-zoo model we build a *measured* workload profile: FLOPs and HBM
bytes come from the jitted train step's ``cost_analysis()`` (CNNs have no
while loops, so XLA's numbers are exact here), wall time per step is
measured on this host, and the paper's GPU rigs are then driven by the
calibrated ``PowerCappedDevice`` model (DESIGN.md Sec 5) — physics-first,
not outcome-fitted: the paper's phenomenology has to *emerge*.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (PowerCappedDevice, RTX_3080, RTX_3090,
                        WorkloadProfile)
from repro.data import CifarBatches
from repro.models.cnn import CNN_ZOO, cnn_loss
from repro.optim import OptimizerConfig, adamw_init, adamw_update

CIFAR_TRAIN_SIZE = 50_000


@dataclasses.dataclass
class ModelRun:
    name: str
    flops_per_step: float            # fwd+bwd, batch of `batch`
    bytes_per_step: float
    batch: int
    wall_s_per_step: float           # on this host (CPU) — Fig 3 baseline
    accuracy: float                  # after `train_steps` on synthetic CIFAR
    n_params: int

    def workload(self, samples_per_step: int | None = None) -> WorkloadProfile:
        return WorkloadProfile(
            name=self.name,
            flops_per_step=self.flops_per_step,
            hbm_bytes_per_step=self.bytes_per_step,
            samples_per_step=samples_per_step or self.batch,
        )


def _make_step(apply_fn, opt_cfg):
    def step(params, opt, images, labels):
        loss, grads = jax.value_and_grad(
            lambda p: cnn_loss(apply_fn, p, images, labels))(params)
        params, opt, _ = adamw_update(grads, opt, params, opt_cfg)
        return params, opt, loss
    return jax.jit(step)


_PROFILE_CACHE: dict = {}
_CACHE_DIR = __import__("pathlib").Path(__file__).resolve().parents[1] \
    / "artifacts" / "cnn_profiles"


def profile_cnn(name: str, *, batch: int = 32, train_steps: int = 12,
                eval_batches: int = 2, seed: int = 0,
                time_steps: int = 3) -> ModelRun:
    """Measure one zoo model: flops/bytes (XLA), wall time, short-train acc.

    Profiles are cached (in-process + on disk) — fig2/fig4/fig6 all profile
    the same zoo, and compiles dominate the cost.  The key names the device
    (platform and ``device_kind``), so a profile timed on one device is
    never read back on another.
    """
    import json as _json
    dev = jax.devices()[0]
    device = f"{dev.platform}-{dev.device_kind}".replace(" ", "_")
    key = (name, batch, train_steps, seed, device)
    if key in _PROFILE_CACHE:
        return _PROFILE_CACHE[key]
    fkey = _CACHE_DIR / f"{name}_{batch}_{train_steps}_{seed}_{device}.json"
    if fkey.exists():
        run = ModelRun(**_json.loads(fkey.read_text()))
        _PROFILE_CACHE[key] = run
        return run
    run = _profile_cnn_uncached(name, batch=batch, train_steps=train_steps,
                                eval_batches=eval_batches, seed=seed,
                                time_steps=time_steps)
    _PROFILE_CACHE[key] = run
    _CACHE_DIR.mkdir(parents=True, exist_ok=True)
    fkey.write_text(_json.dumps(dataclasses.asdict(run)))
    return run


def _profile_cnn_uncached(name: str, *, batch: int = 32, train_steps: int = 12,
                          eval_batches: int = 2, seed: int = 0,
                          time_steps: int = 3) -> ModelRun:
    init, apply = CNN_ZOO[name]
    params = init(jax.random.PRNGKey(seed))
    opt_cfg = OptimizerConfig(learning_rate=1e-3, warmup_steps=2,
                              total_steps=train_steps, weight_decay=0.0,
                              schedule="constant")
    opt = adamw_init(params, opt_cfg)
    data = CifarBatches(seed=seed, batch=batch)
    step = _make_step(apply, opt_cfg)

    x0, y0 = data.batch_at(0)
    lowered = step.lower(params, opt, jnp.asarray(x0), jnp.asarray(y0))
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    nbytes = float(cost.get("bytes accessed", 0.0))

    # train briefly (synthetic CIFAR is separable: accuracy rises fast)
    t_acc = 0.0
    n_timed = 0
    for i in range(train_steps):
        x, y = data.batch_at(i)
        t0 = time.perf_counter()
        params, opt, loss = step(params, opt, jnp.asarray(x), jnp.asarray(y))
        jax.block_until_ready(loss)
        if i >= train_steps - time_steps:          # steady-state timing
            t_acc += time.perf_counter() - t0
            n_timed += 1

    # eval
    correct = total = 0
    for i in range(100, 100 + eval_batches):
        x, y = data.batch_at(i)
        logits = apply(params, jnp.asarray(x))
        correct += int(jnp.sum(jnp.argmax(logits, -1) == jnp.asarray(y)))
        total += y.size
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    return ModelRun(name=name, flops_per_step=flops, bytes_per_step=nbytes,
                    batch=batch, wall_s_per_step=t_acc / max(n_timed, 1),
                    accuracy=correct / total, n_params=n_params)


def epoch_quantities(run: ModelRun, device: PowerCappedDevice,
                     cap: float = 1.0, batch: int = 128):
    """(energy_J, time_s, mean_power_W, utilization) for ONE CIFAR epoch on
    the simulated rig, scaling the measured per-step profile to `batch`."""
    scale = batch / run.batch
    wl = WorkloadProfile(
        name=run.name,
        flops_per_step=run.flops_per_step * scale,
        hbm_bytes_per_step=run.bytes_per_step * scale,
        samples_per_step=batch,
    )
    est = device.estimate(wl, cap)
    steps = CIFAR_TRAIN_SIZE / batch
    return (est.energy_j * steps, est.step_time_s * steps, est.power_w,
            est.utilization)


def pearson(x, y) -> float:
    x = np.asarray(x, dtype=np.float64)
    y = np.asarray(y, dtype=np.float64)
    if x.std() == 0 or y.std() == 0:
        return 0.0
    return float(np.corrcoef(x, y)[0, 1])


SETUP1 = PowerCappedDevice(RTX_3080)      # paper setup no.1
SETUP2 = PowerCappedDevice(RTX_3090)      # paper setup no.2

ZOO_ORDER = list(CNN_ZOO)


def profile_zoo(models=None, **kw) -> dict[str, ModelRun]:
    out = {}
    for name in (models or ZOO_ORDER):
        out[name] = profile_cnn(name, **kw)
    return out
