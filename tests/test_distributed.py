"""Distributed-parity tests: run in a SUBPROCESS with 8 forced host devices
(so the main pytest process keeps its single real device), asserting that

  * the sharded (2x4 mesh FSDP x TP) train step produces the same loss and
    updated params as the unsharded step,
  * the shard_map MoE path matches the no-mesh dispatch bit-for-bit in
    routing decisions,
  * decode with sharded caches matches unsharded decode.
"""
import json
import os
import pathlib
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from repro.configs import ARCH_SPECS
from repro.models import transformer as tfm
from repro.models.transformer import RunCtx
from repro.optim import OptimizerConfig
from repro.optim.adamw import opt_state_sharding
from repro.runtime.sharding import batch_sharding, build_rules, cache_sharding
from repro.runtime.steps import StepConfig, init_train_state, make_train_step, make_serve_step
from jax.sharding import NamedSharding, PartitionSpec
from repro.launch.mesh import make_mesh

results = {}
mesh = make_mesh((2, 4), ("data", "model"))

for arch_id in ["smollm-135m", "phi3.5-moe-42b-a6.6b", "mamba2-370m",
                "zamba2-1.2b", "deepseek-v2-236b"]:
    cfg = ARCH_SPECS[arch_id].smoke
    step_cfg = StepConfig(n_micro=1, remat="none",
                          optimizer=OptimizerConfig(learning_rate=1e-3,
                                                    warmup_steps=1,
                                                    total_steps=10))
    key = jax.random.PRNGKey(0)
    toks = jax.random.randint(jax.random.PRNGKey(1),
                              ((4, 16, cfg.n_codebooks) if cfg.n_codebooks
                               else (4, 16)), 0, cfg.vocab_size)
    batch = {"inputs": toks, "targets": toks}
    if cfg.vision_tokens:
        batch["image_embeds"] = jax.random.normal(
            key, (4, cfg.vision_tokens, cfg.d_model), jnp.bfloat16)

    # -- unsharded reference ------------------------------------------------
    state0, axes = init_train_state(key, cfg, step_cfg)
    ref_step = jax.jit(make_train_step(cfg, step_cfg))
    ref_state, ref_m = ref_step(jax.tree.map(lambda x: x, state0), batch)

    # -- sharded --------------------------------------------------------------
    rules = build_rules(cfg, mesh)
    psh = rules.param_sharding(axes)
    rep = NamedSharding(mesh, PartitionSpec())
    state_sh = {"params": psh,
                "opt": opt_state_sharding(psh, state0["opt"], mesh),
                "step": rep}
    bsh = batch_sharding(rules, batch)
    state_p = jax.device_put(state0, state_sh)
    batch_p = jax.device_put(batch, bsh)
    with mesh:
        sh_step = jax.jit(make_train_step(cfg, step_cfg, rules),
                          in_shardings=(state_sh, bsh),
                          out_shardings=(state_sh, None))
        sh_state, sh_m = sh_step(state_p, batch_p)

    dloss = abs(float(ref_m["loss"]) - float(sh_m["loss"]))
    dg = abs(float(ref_m["grad_norm"]) - float(sh_m["grad_norm"])) \
        / max(float(ref_m["grad_norm"]), 1e-9)
    # updated params parity (max over leaves of max-abs diff)
    dmax = 0.0
    for a, b in zip(jax.tree.leaves(ref_state["params"]),
                    jax.tree.leaves(sh_state["params"])):
        dmax = max(dmax, float(jnp.max(jnp.abs(a - np.asarray(b)))))
    results[arch_id] = {"dloss": dloss, "dgrad": dg, "dparam": dmax,
                        "route_limited": bool(cfg.route_group_limit)}

# -- decode parity on one arch with sharded caches ----------------------------
cfg = ARCH_SPECS["h2o-danube-3-4b"].smoke
params, axes = tfm.init_lm(jax.random.PRNGKey(0), cfg)
ctx = RunCtx()
toks = jax.random.randint(jax.random.PRNGKey(2), (4, 8), 0, cfg.vocab_size)
_, cache = tfm.prefill(params, toks, cfg, ctx, max_len=16)
tok_new = toks[:, :1]
ref_logits, _ = tfm.decode_step(params, cache, tok_new, cfg, ctx)

rules = build_rules(cfg, mesh)
psh = rules.param_sharding(axes)
csh = cache_sharding(rules, cache, cfg)
with mesh:
    serve = jax.jit(make_serve_step(cfg, StepConfig(), rules, greedy=False),
                    in_shardings=(psh, csh, batch_sharding(rules, tok_new)))
    sh_logits, _ = serve(jax.device_put(params, psh),
                         jax.device_put(cache, csh),
                         jax.device_put(tok_new, batch_sharding(rules, tok_new)))
results["decode_parity"] = {
    "dlogit": float(jnp.max(jnp.abs(ref_logits - np.asarray(sh_logits))))}

print("RESULTS_JSON=" + json.dumps(results))
"""


@pytest.mark.slow
def test_sharded_equals_unsharded():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, f"subprocess failed:\n{out.stderr[-3000:]}"
    line = [l for l in out.stdout.splitlines()
            if l.startswith("RESULTS_JSON=")][-1]
    results = json.loads(line.split("=", 1)[1])
    for arch, r in results.items():
        if arch == "decode_parity":
            assert r["dlogit"] < 0.1, f"decode mismatch: {r}"
            continue
        # bf16 activations + different psum reduction orders: ~1e-2 slack
        assert r["dloss"] < 2e-2, f"{arch} loss mismatch: {r}"
        # DeepSeek's device-limited routing (route_group_limit) only engages
        # on a mesh, so the sharded run deliberately routes a few tokens to
        # different experts than the no-mesh reference — grad norms diverge
        # beyond numerics while loss/params stay in parity.  Measured on this
        # jax: dgrad 0.091 with routing limited, 0.011 with the limit
        # disabled — the bound covers the former with margin, not a blanket
        # relaxation (only deepseek-v2 sets route_group_limit).
        dgrad_bound = 0.12 if r.get("route_limited") else 0.05
        assert r["dgrad"] < dgrad_bound, f"{arch} grad-norm mismatch: {r}"
        assert r["dparam"] < 2e-2, f"{arch} param mismatch: {r}"


_LAUNCH = {
    "serve": (["repro.launch.serve", "--traffic", "poisson", "--requests",
               "4"], "[serve] engine: 4 requests"),
    "train": (["repro.launch.train", "--steps", "3", "--seq", "32"],
              "[train] 3 steps"),
}


@pytest.mark.parametrize("launcher", sorted(_LAUNCH))
def test_launcher_runs_on_four_host_devices(launcher, tmp_path):
    """Each launcher's CLI runs to its summary on a host that shows four
    devices: serve keeps its one-device engine, train shards its step over
    a mesh of all four."""
    args, summary = _LAUNCH[launcher]
    env = dict(os.environ)
    env["PYTHONPATH"] = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    if launcher == "train":
        args = args + ["--ckpt-dir", str(tmp_path / "ckpt")]
    out = subprocess.run([sys.executable, "-m", *args, "--arch",
                          "smollm-135m", "--smoke"], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"{launcher} failed:\n{out.stderr[-3000:]}"
    assert summary in out.stdout, out.stdout[-3000:]
    if launcher == "train":
        assert "over 4 devices" in out.stdout, out.stdout[-3000:]
    else:
        finished = [l for l in out.stdout.splitlines() if "  rid=" in l]
        assert len(finished) == 4, out.stdout[-3000:]
        assert all("fin=max_new_tokens" in l for l in finished), finished
