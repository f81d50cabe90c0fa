"""Chip-compiler compiles of the main path's kernels at real widths.

Each test compiles one Pallas kernel with the TPU compiler against a
described (not attached) ``v5e:2x2`` topology: the compiler refuses what
interpret mode accepts (unaligned slices, too much VMEM, unsupported
operands), so these guard the kernels at no chip time.  Nothing runs.

The topology is described inside a module-scoped fixture, never at import
time: only one process may load the TPU library, and every test worker
imports this file.  Where it cannot be described the tests skip.
"""
import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import (flash_attention_diff,
                                           flash_attention_pallas)
from repro.kernels.odc_gather import odc_gather_pallas
from repro.kernels.odc_scatter import odc_scatter_accumulate_pallas

# qwen-1.5b attention: 12 query heads over 2 kv heads of 128; a 2048-token
# packed sequence
B, S, H, KH, HD = 1, 2048, 12, 2, 128
# one MLP weight shard of qwen-1.5b: w_up (1536, 8960) f32 over 4 chips
SHARD = (1536 // 4, 8960)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    # a compile for a described chip cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def ring(topo):
    return Mesh(np.asarray(topo.devices), ("x",))


def _attn_args(sharding):
    q = jax.ShapeDtypeStruct((B, S, H, HD), jnp.float32, sharding=sharding)
    kv = jax.ShapeDtypeStruct((B, S, KH, HD), jnp.float32, sharding=sharding)
    return q, kv, kv


def _has_kernel(compiled):
    return "tpu_custom_call" in compiled.as_text()


def test_flash_attention_forward_compiles(one_chip):
    fwd = jax.jit(lambda q, k, v: flash_attention_pallas(
        q, k, v, causal=True, interpret=False))
    assert _has_kernel(fwd.lower(*_attn_args(one_chip)).compile())


def test_flash_attention_diff_forward_and_backward_compile(one_chip):
    def loss(q, k, v):
        out = flash_attention_diff(q, k, v, causal=True, interpret=False)
        return jnp.sum(out * out)

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    assert _has_kernel(step.lower(*_attn_args(one_chip)).compile())


def test_odc_ring_gather_compiles_at_layer_shard(ring):
    n = ring.shape["x"]
    fn = jax.shard_map(
        lambda x: odc_gather_pallas(x, axis_name="x", interpret=False)[None],
        mesh=ring, in_specs=P("x"), out_specs=P("x"), check_vma=False)
    x = jax.ShapeDtypeStruct((n * SHARD[0], SHARD[1]), jnp.float32,
                             sharding=NamedSharding(ring, P("x")))
    compiled = jax.jit(fn).lower(x).compile()
    assert _has_kernel(compiled)
    # per device: the gathered layer, and no staging copy beside it
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == n * SHARD[0] * SHARD[1] * 4
    assert mem.temp_size_in_bytes == 0


def test_odc_ring_scatter_accumulate_compiles_at_layer_shard(ring):
    n = ring.shape["x"]
    fn = jax.shard_map(
        lambda y: odc_scatter_accumulate_pallas(y[0], axis_name="x",
                                                interpret=False),
        mesh=ring, in_specs=P("x"), out_specs=P("x"), check_vma=False)
    y = jax.ShapeDtypeStruct((n, n) + SHARD, jnp.float32,
                             sharding=NamedSharding(ring, P("x")))
    assert _has_kernel(jax.jit(fn).lower(y).compile())


@pytest.fixture(scope="module")
def step_hlo(topo):
    """The chip's compiled training step: reduced qwen-1.5b on one chip,
    minibatch schedule, M = 2 microbatches of 64 tokens."""
    from repro.configs import get_reduced
    from repro.core import gspmd

    cfg = get_reduced("qwen-1.5b")
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    gcfg = gspmd.GSPMDConfig(rules=gspmd.ShardingRules(),
                             schedule="minibatch", comm="odc", block_kv=32)
    shapes = {k: jax.ShapeDtypeStruct((2, 1, 64), dt) for k, dt in (
        ("tokens", jnp.int32), ("targets", jnp.int32),
        ("positions", jnp.int32), ("segment_ids", jnp.int32),
        ("loss_mask", jnp.float32))}
    jitted, args = gspmd.build_train_artifacts(cfg, mesh, gcfg, shapes)
    return jitted.lower(*args).compile().as_text()


def test_step_program_names_every_matmul(step_hlo):
    """Every matmul of the chip's compiled training step carries exactly
    one of the program's block scopes, which the benchmark's trace
    reduction reads."""
    blocks = {"attention", "mlp", "lm_head"}
    matmuls = [line for line in step_hlo.splitlines()
               if re.search(r" (dot|convolution)\(", line)]
    assert len(matmuls) >= 30
    seen = set()
    for line in matmuls:
        op = re.search(r'op_name="([^"]*)"', line)
        assert op, line
        names = {re.sub(r"^(?:[\w.-]+\()+([^()]*)\)*$", r"\1", seg)
                 for seg in op.group(1).split("/")}
        assert len(blocks & names) == 1, op.group(1)
        seen |= blocks & names
    assert seen == blocks


def test_minibatch_step_recomputes_only_the_layers(step_hlo):
    """The compiled minibatch step's matmul FLOPs by pass: each
    microbatch's forward runs once, so the only recompute is the layer
    checkpoint's.  It redoes attention whole and the MLP's gate and up
    projections (the down projection's output no backward reads), and
    nothing of the head."""
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from bench.tests.test_scopes import matmul_flops

    own, _ = matmul_flops(step_hlo)
    assert own["forward/attention"] > 0 and own["forward/mlp"] > 0
    assert own["recompute/attention"] == own["forward/attention"]
    assert 3 * own["recompute/mlp"] == 2 * own["forward/mlp"]
    assert own["recompute/lm_head"] == 0
    for b in ("attention", "mlp", "lm_head"):
        assert own[f"backward/{b}"] == 2 * own[f"forward/{b}"]


def _fusions(hlo):
    """(output shape, op_name, fused computation's lines) of every fusion
    of an HLO text."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.-]+) .*\{$", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    out = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (\w+\[[\d,]*\])\S* fusion\(.*"
                     r"calls=%([\w.-]+)", line)
        if m:
            op = re.search(r'op_name="([^"]*)"', line)
            out.append((m.group(1), op.group(1) if op else "",
                        comps[m.group(2)]))
    return out


def _stacked_layer_shapes():
    """The reduced qwen-1.5b step's stacked layer leaves, as HLO shapes:
    {shape: leaves}, and the matmul weights' among them."""
    from repro.configs import get_reduced
    from repro.models import transformer as T

    params = jax.eval_shape(lambda: T.init_params(
        get_reduced("qwen-1.5b"), jax.random.PRNGKey(0)))
    shapes = [f"f32[{','.join(map(str, x.shape))}]"
              for x in jax.tree.leaves(params["layers"])]
    weights = [s for s, x in zip(shapes, jax.tree.leaves(params["layers"]))
               if x.ndim == 3]
    return set(shapes), weights


def test_no_pass_over_a_stacked_gradient_after_the_layer_loop(step_hlo):
    """The minibatch loop's gradient sum is folded into the layer loop's
    backward: no fusion of the microbatch loop adds a whole stacked layer
    gradient to the running sum after the layer loop has written it."""
    stacked, _ = _stacked_layer_shapes()
    outer = [(shape, op) for shape, op, _ in _fusions(step_hlo)
             if shape in stacked and op.endswith("while/body/closed_call/add")]
    assert not outer


def test_each_stacked_weight_gradient_adds_into_the_running_sum(step_hlo):
    """Each stacked weight's gradient matmul in the layer loop's backward
    writes the running sum's slice plus its gradient in place: the fusion
    reads the slice of the stack it updates, adds the matmul, and writes
    the slice back."""
    _, weights = _stacked_layer_shapes()
    folded = []
    for shape, op, body in _fusions(step_hlo):
        text = "\n".join(body)
        if (shape in weights and "transpose(" in op
                and " convolution(" in text):
            assert re.search(r" dynamic-slice\(%param_0", text), op
            assert " add(" in text, op
            root = next(x for x in body if x.lstrip().startswith("ROOT "))
            assert " dynamic-update-slice(%param_0" in root, op
            folded.append(shape)
    assert sorted(folded) == sorted(weights)


def test_expert_layer_kernels_compile_under_their_scope(one_chip,
                                                        monkeypatch):
    """DeepSeek-V2-Lite's held expert layer at real widths (8 held of 64,
    d 2,048, width 1,408, 1,024 tokens, float32), forward and backward:
    its grouped matmuls compile as the megablox kernels within the scoped
    VMEM, and each keeps the ``moe`` block on its ``op_name``, which the
    benchmark's trace reduction reads (XLA's kernel for
    ``jax.lax.ragged_dot`` drops it)."""
    import dataclasses

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from bench.harness import scopes
    from repro.configs import get_config
    from repro.models import moe

    monkeypatch.setattr(moe, "_kernel", lambda: False)
    cfg = dataclasses.replace(get_config("deepseek-v2-lite"), experts_held=8)
    p = jax.eval_shape(lambda: moe.moe_params(jax.random.PRNGKey(0), cfg,
                                              jnp.float32))
    x = jax.ShapeDtypeStruct((1, 1024, 2048), jnp.float32,
                             sharding=one_chip)
    p = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                    sharding=one_chip), p)

    def loss(p, x):
        with jax.named_scope("moe"):
            return jnp.sum(moe.moe_apply_grouped(cfg, p, x)[0])

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(p, x).compile(
        ).as_text()
    assert "ragged-dot" not in hlo
    names = scopes.op_names(hlo)
    kernels = [n for n in names if re.match(r"t?gmm(\.\d+)?$", n)]
    # up, gate, down: forward, x's gradient, the weights' gradient
    assert len(kernels) == 9, sorted(names)
    for n in kernels:
        assert scopes.label(names[n]).endswith("/moe"), names[n]
