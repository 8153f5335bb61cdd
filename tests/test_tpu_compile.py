"""Compile the main path's Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers and compiles for a chip that is described,
not attached, which is what the chip's compiler would refuse (tiling,
VMEM, memory) without spending chip time.  The topology is described in a
module fixture, never at import, so every test worker collects the same
tests and only the worker given this file loads the TPU library.
"""
from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels import decode_attention as da
from repro.kernels.ops import KernelPolicy
from repro.models import transformer as tfm
from repro.runtime.steps import (StepConfig, make_paged_decode_loop,
                                 make_prefill_suffix_step)

HBM_BYTES = 16 * 10**9          # one v5e chip

# SmolLM-135M serving shapes: 16 slots x 2048 tokens in pages of 16
B, HQ, HKV, D, PS, NB, Q = 16, 9, 3, 64, 16, 128, 4
N_PAGES = B * NB + B
# StableLM-2-1.6B batch shapes: 8 slots x 2048 tokens, MHA 32/32
SB, SHQ = 8, 32
S_PAGES = SB * NB + SB
# DeepSeek-V2 latent widths at batch 1
MLA_HQ, MLA_R, MLA_RKV, MLA_NB = 128, 576, 512, 256


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _hlo_with_operand_shapes(compiled) -> str:
    """The compiled module's text with each operand's shape, as a device
    trace names an op."""
    from jax._src.lib import xla_client
    opts = xla_client._xla.HloPrintOptions()
    opts.print_operand_shape = True
    return compiled.runtime_executable().hlo_modules()[0].to_string(opts)


def _decode_sweep_signature(b: int, hq: int, d: int, nb: int):
    """The HLO signature the benchmark's kernel-roofline reader picks the
    paged decode sweep by: result ``bf16[B,Hq,1,D]``, operands starting
    with the ``s32[B,nb]`` block table and the ``s32[B]`` positions."""
    import re
    return re.compile(
        r"= bf16\[%d,%d,1,%d\]\{[^}]*\} custom-call\(s32\[%d,%d\]\{[^}]*\} "
        r"[^,]+, s32\[%d\]" % (b, hq, d, b, nb, b))


def _kernels(compiled) -> set[str]:
    """The names the compiled program gives its Pallas kernels (the
    instruction names a device trace shows)."""
    return {line.split("=", 1)[0].strip().lstrip("%").split(".", 1)[0]
            for line in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in line}


def _gqa_case(one_chip, name: str, n_splits: int):
    S = lambda shape, dt=jnp.bfloat16: _shape(one_chip, shape, dt)
    pool, bt = S((N_PAGES, PS, HKV, D)), S((B, NB), jnp.int32)
    pos, ring = S((B,), jnp.int32), S((B, NB * PS, HKV, D))
    qv, new = S((B, Q, HQ, D)), S((B, Q, HKV, D))
    p0 = S((), jnp.int32)
    if name == "paged_decode":
        return (lambda q, k, v, t, p: da.paged_decode_attention_pallas(
            q, k, v, t, p, n_splits=n_splits),
            S((B, 1, HQ, D)), pool, pool, bt, pos)
    if name == "paged_decode_int8":
        i8, sc = S((N_PAGES, PS, HKV, D), jnp.int8), \
            S((N_PAGES, PS, HKV, 1), jnp.float32)
        return (lambda q, k, v, ks, vs, t, p: da.paged_decode_attention_pallas(
            q, k, v, t, p, n_splits=n_splits, k_scale=ks, v_scale=vs),
            S((B, 1, HQ, D)), i8, i8, sc, sc, bt, pos)
    if name == "paged_verify":
        return (lambda q, k, v, kn, vn, t, p: da.paged_verify_attention_pallas(
            q, k, v, kn, vn, t, p, n_splits=n_splits),
            qv, pool, pool, new, new, bt, pos)
    if name == "ring_decode":
        return (lambda q, k, v, p: da.decode_attention_pallas(
            q, k, v, p, n_splits=n_splits),
            S((B, 1, HQ, D)), ring, ring, p0)
    if name == "ring_verify":
        return (lambda q, k, v, kn, vn, p: da.verify_attention_pallas(
            q, k, v, kn, vn, p, n_splits=n_splits),
            qv, ring, ring, new, new, p0)
    raise KeyError(name)


@pytest.mark.parametrize("n_splits", [1, 2])
@pytest.mark.parametrize("name", ["paged_decode", "paged_decode_int8",
                                  "paged_verify", "ring_decode",
                                  "ring_verify"])
def test_gqa_sweep_compiles(one_chip, name, n_splits):
    fn, *args = _gqa_case(one_chip, name, n_splits)
    family = name.removesuffix("_int8")
    assert _kernels(_compile(fn, *args)) == (
        {f"{family}_sweep"} if n_splits == 1
        else {f"{family}_partials", "split_kv_merge"})


@pytest.mark.parametrize("cell", ["smollm_chat", "stablelm_batch"])
def test_paged_decode_sweep_signature(one_chip, cell):
    """The single-stage paged decode sweep at each benchmark cell's shape
    compiles, is named, keeps the signature the benchmark reads, and takes
    the pool as stored: no transposed or relaid-out pool feeds it."""
    b, hq, hkv, n_pages = ((B, HQ, HKV, N_PAGES) if cell == "smollm_chat"
                           else (SB, SHQ, SHQ, S_PAGES))
    S = lambda shape, dt=jnp.bfloat16: _shape(one_chip, shape, dt)
    compiled = _compile(
        lambda q, k, v, t, p: da.paged_decode_attention_pallas(q, k, v, t, p),
        S((b, 1, hq, D)), S((n_pages, PS, hkv, D)), S((n_pages, PS, hkv, D)),
        S((b, NB), jnp.int32), S((b,), jnp.int32))
    assert _kernels(compiled) == {"paged_decode_sweep"}
    text = _hlo_with_operand_shapes(compiled)
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1
    assert _decode_sweep_signature(b, hq, D, NB).search(calls[0])
    assert f"bf16[{n_pages},{hkv},{PS},{D}]" not in text


def test_paged_decode_grid_steps():
    """Grid steps per call are bounded by slots x page blocks x splits at
    both cells' shapes, and a step covers 128-512 tokens there."""
    for b, hkv in ((B, HKV), (SB, SHQ)):
        ppc = da.pages_per_block(PS, hkv, D, 2, NB)
        assert 128 <= ppc * PS <= 512
        for n_splits in (None, 1, 2, 5):
            grid = da.paged_decode_grid(b, NB, ppc, n_splits)
            steps = 1
            for n in grid:
                steps *= n
            assert steps <= b * -(-NB // ppc) * (n_splits or 1)
            assert grid[0] == b and len(grid) == (2 if n_splits is None
                                                  else 3)


@pytest.mark.parametrize("n_splits", [1, 4])
def test_mla_paged_decode_compiles(one_chip, n_splits):
    S = lambda shape, dt=jnp.bfloat16: _shape(one_chip, shape, dt)
    compiled = _compile(
        lambda q, lat, t, p: da.mla_paged_decode_attention_pallas(
            q, lat, t, p, r_kv=MLA_RKV, n_splits=n_splits, scale=192 ** -0.5),
        S((1, 1, MLA_HQ, MLA_R)), S((MLA_NB + 8, PS, MLA_R)),
        S((1, MLA_NB), jnp.int32), S((1,), jnp.int32))
    assert _kernels(compiled) == (
        {"mla_paged_decode_sweep"} if n_splits == 1
        else {"mla_paged_decode_partials", "split_kv_merge"})


def test_merge_kernel_compiles(one_chip):
    compiled = _compile(
        lambda p, l: da.merge_kv_splits_pallas(p, l, out_dtype=jnp.bfloat16),
        _shape(one_chip, (B, HQ, 4, 1, D), jnp.float32),
        _shape(one_chip, (B, HQ, 4, 1), jnp.float32))
    assert _kernels(compiled) == {"split_kv_merge"}


def test_smollm_paged_decode_loop_compiles_and_fits(one_chip):
    """The engine's fused decode loop at SmolLM-135M's published widths,
    16 slots x 2048 tokens, with the Pallas decode sweep."""
    cfg = get_arch("smollm-135m").config
    step_cfg = StepConfig(remat="none",
                          kernel_policy=KernelPolicy(decode="pallas"))
    place = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    params = place(jax.eval_shape(
        lambda: tfm.init_lm(jax.random.PRNGKey(0), cfg)[0]))
    cache = place(jax.eval_shape(
        lambda: tfm.init_paged_cache(cfg, B, N_PAGES, PS, NB)))
    loop = jax.jit(make_paged_decode_loop(cfg, step_cfg, n_tokens=8),
                   donate_argnums=(1,))
    compiled = loop.lower(params, cache,
                          _shape(one_chip, (B, 1), jnp.int32),
                          _shape(one_chip, (B,), jnp.int32)).compile()
    assert compiled.as_text().startswith("HloModule jit_decode_loop")
    assert _kernels(compiled) == {"paged_decode_sweep"}
    # the sweep reads the pool as stored: no instruction of the loop
    # produces a head-major (transposed) pool
    assert f"bf16[{N_PAGES},{HKV},{PS},{D}]" not in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, f"decode loop needs {total / 1e9:.2f} GB"


def test_smollm_suffix_step_names_its_kernel(one_chip):
    """The engine's chunked suffix-prefill sweep at SmolLM-135M's widths:
    a program named after its function, with the named verify kernel the
    benchmark's suffix-sweep reader counts."""
    cfg = get_arch("smollm-135m").config
    step_cfg = StepConfig(remat="none",
                          kernel_policy=KernelPolicy(decode="pallas"))
    place = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    params = place(jax.eval_shape(
        lambda: tfm.init_lm(jax.random.PRNGKey(0), cfg)[0]))
    cache = place(jax.eval_shape(
        lambda: tfm.init_paged_cache(cfg, B, N_PAGES, PS, NB)))
    step = jax.jit(make_prefill_suffix_step(cfg, step_cfg),
                   donate_argnums=(1,))
    compiled = step.lower(params, cache,
                          _shape(one_chip, (B, 16), jnp.int32),
                          _shape(one_chip, (B,), jnp.int32)).compile()
    assert compiled.as_text().startswith("HloModule jit_suffix_step")
    assert _kernels(compiled) == {"paged_verify_sweep"}


def test_deepseek_lite_expert_layer_compiles_to_grouped_matmuls(one_chip):
    """DeepSeek-V2-Lite's routed-expert layer at its published widths and
    the decode shapes of its benchmark cell (64 slots, 8 of 64 experts
    held): three grouped matmuls, with the operand and result shapes the
    benchmark's ``moe_roofline.long_batch`` reader picks them by, and no
    dense all-expert matmul."""
    import re
    from repro.models.layers import moe_forward
    cfg = dataclasses.replace(get_arch("deepseek-v2-lite").config,
                              held_experts=8)
    ffn = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype,
                                       sharding=one_chip),
        jax.eval_shape(lambda: tfm.init_lm(jax.random.PRNGKey(0),
                                           cfg)[0])["layers"]["sub0"]["ffn"])
    compiled = _compile(lambda p, x: moe_forward(p, x, cfg)[::2], ffn,
                        _shape(one_chip, (64, 1, 2048), jnp.bfloat16))
    text = _hlo_with_operand_shapes(compiled)
    rows, d, ff = 64 * 6, 2048, 1408
    shape = r"bf16\[%d,%d\]\{[^}]*\} custom-call\(.*"
    gate_up = re.compile(r"= " + shape % (rows, ff)
                         + r"bf16\[8,%d,%d\]" % (d, ff))
    down = re.compile(r"= " + shape % (rows, d) + r"bf16\[8,%d,%d\]" % (ff, d))
    lines = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert sum(1 for ln in lines if gate_up.search(ln)) == 2
    assert sum(1 for ln in lines if down.search(ln)) == 1
    assert f"[8,{rows},{ff}]" not in text


def test_deepseek_lite_latent_sweep_compiles_at_the_cells_batch(one_chip):
    """The latent sweep at DeepSeek-V2-Lite's 16 heads, 64 slots x 4096
    tokens in pages of 16, as ``mla_roofline.long_batch`` names it."""
    S = lambda shape, dt=jnp.bfloat16: _shape(one_chip, shape, dt)
    compiled = _compile(
        lambda q, lat, t, p: da.mla_paged_decode_attention_pallas(
            q, lat, t, p, r_kv=MLA_RKV, n_splits=1, scale=192 ** -0.5),
        S((64, 1, 16, MLA_R)), S((64 * 256 + 64, PS, MLA_R)),
        S((64, 256), jnp.int32), S((64,), jnp.int32))
    assert _kernels(compiled) == {"mla_paged_decode_sweep"}
