"""The model-FLOP function against a count by hand, for both models, and
what each configuration file builds: the program's ModelConfig and the
FLOPs that ``mfu`` reads, through the configuration's reference module."""
import dataclasses
import json
from pathlib import Path

import pytest

from bench.harness import cell as C
from bench.reference import dense_decoder as flops
from repro.configs import get_config

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def run(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["run"]


def test_qwen_d8_by_hand():
    # per layer: q 1536x1536, k and v 1536x256 each, o 1536x1536,
    # gate/up/down 3 x 1536x8960; head (tied) 1536x151936
    per_layer = 1536 * 1536 * 2 + 1536 * 256 * 2 + 3 * 1536 * 8960
    assert per_layer == 46_792_704
    params = 8 * per_layer + 1536 * 151_936
    assert flops.matmul_params(run("qwen-1.5b-d8")) == params
    # one sample of 100 tokens: 5050 causal pairs, 12 heads of 128,
    # 2 matmuls x 2 FLOPs forward, x3 with the backward, 8 layers
    attn = 8 * 3 * 2 * 2 * 128 * 12 * 5050
    assert flops.step_flops(run("qwen-1.5b-d8"), [100]) == 6 * params * 100 + attn


def test_phi3_d1_by_hand():
    # q 5120x5120, k and v 5120x1280 each, o 5120x5120, 3 x 5120x17920,
    # untied head 5120x32064
    per_layer = 5120 * 5120 * 2 + 5120 * 1280 * 2 + 3 * 5120 * 17920
    params = per_layer + 5120 * 32_064
    assert flops.matmul_params(run("phi3-medium-14b-d1")) == params
    two = flops.step_flops(run("phi3-medium-14b-d1"), [3, 4])
    pairs = 6 + 10
    assert two == 6 * params * 7 + 12 * 128 * 40 * pairs


def test_packing_does_not_change_model_flops():
    # attention is counted inside each sample, so the same samples count
    # the same however they are packed or padded
    r = run("qwen-1.5b-d12-fsdp4")
    assert flops.step_flops(r, [10, 20]) == (flops.step_flops(r, [10])
                                             + flops.step_flops(r, [20]))


# the ModelConfig each configuration built before its reference module
# chose it, by hand: the registry entry with every size of the file
BUILT = {
    "qwen-1.5b-d8": ("qwen-1.5b", dict(
        num_layers=8, d_model=1536, num_heads=12, num_kv_heads=2,
        head_dim=128, d_ff=8960, vocab_size=151_936, norm_eps=1e-6,
        rope_theta=10_000.0, tie_embeddings=True)),
    "phi3-medium-14b-d1": ("phi3-medium-14b", dict(
        num_layers=1, d_model=5120, num_heads=40, num_kv_heads=10,
        head_dim=128, d_ff=17920, vocab_size=32_064, norm_eps=1e-5,
        rope_theta=10_000.0, tie_embeddings=False)),
    "qwen-1.5b-d12-fsdp4": ("qwen-1.5b", dict(
        num_layers=12, d_model=1536, num_heads=12, num_kv_heads=2,
        head_dim=128, d_ff=8960, vocab_size=151_936, norm_eps=1e-6,
        rope_theta=10_000.0, tie_embeddings=True)),
}


@pytest.mark.parametrize("name", sorted(BUILT))
def test_each_configuration_builds_the_model_it_built(name):
    conf = json.loads((CONFIGS / f"{name}.json").read_text())
    registry, sizes = BUILT[name]
    assert "program" not in conf
    assert C.model_config(conf) == dataclasses.replace(get_config(registry),
                                                       **sizes)


@pytest.mark.parametrize("workload,config", [
    ("qwen1.5b-sft-longalign-1chip", "qwen-1.5b-d8"),
    ("phi3m-sft-longalign-1chip", "phi3-medium-14b-d1")])
def test_each_cell_counts_the_model_flops_by_hand(workload, config):
    """``Context.model_flops`` of a loaded cell is its reference module's
    count: the hand count above, step by step."""
    cell = C.load_cell(workload)
    assert cell.ref is flops
    r = run(config)
    steps = [C.StepRecord(1, [100], 0.0, 1.0),
             C.StepRecord(2, [3, 4], 0.0, 1.0)]
    ctx = C.Context(run=cell.config["run"], chips=1, peaks=None, setup_s=1.0,
                    window_s=1.0, steps=steps, S=1024, hbm_peak_bytes=0,
                    trace=None, step_flops=cell.ref.step_flops)
    d, f, V = r["hidden_size"], r["intermediate_size"], r["vocab_size"]
    qd = r["num_attention_heads"] * 128
    kvd = r["num_key_value_heads"] * 128
    params = (r["num_hidden_layers"] * (2 * d * qd + 2 * d * kvd + 3 * d * f)
              + d * V)
    attn = r["num_hidden_layers"] * 12 * 128 * r["num_attention_heads"]
    assert ctx.model_flops() == (6 * params * 107
                                 + attn * (5050 + 6 + 10))
