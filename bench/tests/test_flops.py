"""The model-FLOP function against a count by hand, for both models."""
import json
from pathlib import Path

from bench.harness import flops

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
