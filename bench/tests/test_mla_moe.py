"""The DeepSeek-V2-Lite cell: its configuration builds the program it
should, its model FLOPs and its expert roofline count against a count by
hand, a CPU-sized copy of the cell runs end to end through the
``mla_moe`` reference, and ``correct`` is false when the program
renormalizes the top-k or drops at a capacity of 1.0."""
import copy
import dataclasses
import json
import time

import jax
import pytest

from bench.harness import cell as C
from bench.reference import mla_moe
from repro.configs import get_config

WORKLOAD = "dsv2lite-sft-longalign-1chip"
CONFIG = C.BENCH / "configs" / "deepseek-v2-lite-ep8-d5.json"
SEED = 2 ** 31 + 29
# the configuration at a CPU size: 1 dense + 2 expert layers, 4 of 8
# experts held, 3 a token; the program's widths follow through ``program``
TINY_RUN = {"hidden_size": 64, "intermediate_size": 96,
            "num_attention_heads": 4, "num_key_value_heads": 4,
            "num_hidden_layers": 3, "vocab_size": 256, "kv_lora_rank": 16,
            "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
            "moe_intermediate_size": 32, "n_routed_experts": 4,
            "router_outputs": 8, "num_experts_per_tok": 3}
TINY_PROGRAM = {"kv_lora_rank": 16, "qk_nope_head_dim": 8,
                "qk_rope_head_dim": 8, "v_head_dim": 8, "moe_d_ff": 32,
                "shared_expert_d_ff": 64, "num_experts": 8,
                "experts_held": 4, "experts_per_token": 3}


def conf():
    return json.loads(CONFIG.read_text())


def test_the_configuration_builds_the_model_it_names():
    c = conf()
    assert C.model_config(c) == dataclasses.replace(
        get_config("deepseek-v2-lite"), num_layers=5, d_model=2048,
        num_heads=16, num_kv_heads=16, d_ff=10_944, vocab_size=12_800,
        norm_eps=1e-6, rope_theta=10_000, tie_embeddings=False,
        experts_held=8)
    cfg, run = C.model_config(c), c["run"]
    y = run["rope_scaling"]
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (run["kv_lora_rank"], run["qk_nope_head_dim"],
                                run["qk_rope_head_dim"], run["v_head_dim"])
    assert (cfg.num_experts, cfg.experts_held, cfg.experts_per_token) == (
        run["router_outputs"], run["n_routed_experts"],
        run["num_experts_per_tok"])
    assert cfg.moe_d_ff == run["moe_intermediate_size"]
    assert cfg.shared_expert_d_ff == (run["n_shared_experts"]
                                      * run["moe_intermediate_size"])
    assert cfg.first_dense_layers == run["first_k_dense_replace"]
    assert cfg.moe_renormalize == run["norm_topk_prob"]
    assert cfg.moe_aux_per_sequence == run["seq_aux"]
    assert cfg.router_aux_coef == run["aux_loss_alpha"]
    assert cfg.moe_capacity_factor == 0  # dropless
    assert run["routed_scaling_factor"] == 1  # the program has no factor
    assert (cfg.yarn_factor, cfg.yarn_original_max_position,
            cfg.yarn_beta_fast, cfg.yarn_beta_slow, cfg.yarn_mscale,
            cfg.yarn_mscale_all_dim) == (
        y["factor"], y["original_max_position_embeddings"], y["beta_fast"],
        y["beta_slow"], y["mscale"], y["mscale_all_dim"])
    # the catalog's keys at the top level are the sizes as run
    for k, v in c.items():
        if k in run:
            assert run[k] == v, k
    # 535.0 M parameters: 8.56 GB of float32 weights, gradients, AdamW
    assert cfg.num_params() == 535_058_944


def test_model_flops_by_hand():
    run = conf()["run"]
    d, H = 2048, 16
    mla = d * H * 192 + d * 576 + 512 * H * 256 + H * 128 * d
    assert mla == 13_762_560
    routed = 3 * d * 1408 * 6 * 8 // 64  # 0.75 experts a token
    per_token = (d * 12_800 + 5 * mla + 3 * d * 10_944
                 + 4 * (d * 64 + 3 * d * 2816 + routed))
    assert mla_moe.matmul_params_per_token(run) == per_token
    # one sample of 100 tokens: 5050 causal pairs, 16 heads, QK^T over
    # 192 dims and PV over 128, x3 with the backward, 5 layers
    attn = 5 * 3 * 2 * (192 + 128) * 16 * 5050
    assert mla_moe.step_flops(run, [100]) == 6 * per_token * 100 + attn
    assert mla_moe.step_flops(run, [10, 20]) == (
        mla_moe.step_flops(run, [10]) + mla_moe.step_flops(run, [20]))


def test_the_cell_counts_its_model_flops_through_its_module():
    cell = C.load_cell(WORKLOAD)
    assert cell.ref is mla_moe
    steps = [C.StepRecord(1, [100], 0.0, 1.0), C.StepRecord(2, [3, 4], 0.0,
                                                            1.0)]
    ctx = C.Context(run=cell.config["run"], chips=1, peaks=None, setup_s=1.0,
                    window_s=1.0, steps=steps, S=1024, hbm_peak_bytes=0,
                    trace=None, step_flops=cell.ref.step_flops)
    assert ctx.model_flops() == mla_moe.step_flops(cell.config["run"],
                                                   [100, 3, 4])


def test_expert_roofline_count_by_hand():
    work = C.reader("moe_roofline_share").__globals__["work"]
    run = conf()["run"]
    d, f = 2048, 1408
    P = 1024 * 6 * 8 / 64  # 768 pairs a microbatch
    flops = 4 * 2 * (3 * 3 * 2 * P * d * f)  # 4 layers, M = 2
    weights = 8 * 3 * d * f * 4
    nbytes = 4 * 2 * (3 * weights + 5 * P * d * 4)
    assert work(run, 1024, 2) == (flops, nbytes)


def _ctx(scopes, steps=4, m=2):
    return C.Context(run=conf()["run"], chips=1,
                     peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
                     setup_s=1.0, window_s=1.0,
                     steps=[C.StepRecord(m, [10], 0.0, 1.0)] * steps,
                     S=1024, hbm_peak_bytes=0, trace=None,
                     step_flops=mla_moe.step_flops, scopes=scopes)


def test_the_readers_sum_their_block_over_every_pass():
    scopes = {"scopes_s": {"forward/moe": 0.02, "backward/moe": 0.05,
                           "recompute/moe": 0.01, "forward/attention": 0.004,
                           "backward/attention": 0.006, "forward/mlp": 1.0}}
    ctx = _ctx(scopes)
    assert C.reader("moe_device_ms")(ctx) == pytest.approx(20.0)
    assert C.reader("mla_device_ms")(ctx) == pytest.approx(2.5)
    work = C.reader("moe_roofline_share").__globals__["work"]
    flops, nbytes = work(ctx.run, 1024, 2)
    roof = 4 * max(flops / 197e12, nbytes / 819e9)
    assert C.reader("moe_roofline_share")(ctx) == pytest.approx(
        100 * roof / 0.08)
    for name in ("moe_device_ms", "mla_device_ms", "moe_roofline_share"):
        assert C.reader(name)(_ctx(None)) is None
    off_chip = dataclasses.replace(ctx, peaks=None)
    assert C.reader("moe_roofline_share")(off_chip) is None


def tiny_cell(config_file=CONFIG, **program):
    c = C.cell_from_files(WORKLOAD, config_file, "sft-longalign-1k", 1)
    c = copy.deepcopy(c)
    c.config["run"] = dict(c.config["run"], **TINY_RUN)
    c.config["program"] = dict(c.config.get("program", {}), **TINY_PROGRAM,
                               **program)
    c.mix["lengths"] = dict(c.mix["lengths"], rescale_to=64, min_len=4)
    c.mix["microbatch_tokens"] = 64
    c.mix["samples_per_device"] = 4
    c.mix["steps_per_cycle"] = 4
    return c


def run(cell):
    return C.run(cell, SEED, 0.2, False, jax.devices()[:1],
                 time.perf_counter(), log=lambda s: None)


def test_a_tiny_cell_runs_through_its_reference():
    out = run(tiny_cell())
    assert out["correct"], out["compared"]
    assert set(out["compared"]) == {"grad_gap", "change_gap"}
    assert out["failed"] == 0 and out["attempted"] > 0


@pytest.mark.parametrize("fault", [{"moe_renormalize": True},
                                   {"moe_capacity_factor": 1.0}],
                         ids=["renormalized", "capacity-1.0"])
def test_a_planted_fault_reads_not_correct(tmp_path, fault):
    """The fault goes in through the ``program`` object of a temporary
    copy of the configuration file."""
    c = conf()
    c["program"] = dict(c["program"], **fault)
    path = tmp_path / CONFIG.name
    path.write_text(json.dumps(c))
    out = run(tiny_cell(path))
    assert not out["correct"], out["compared"]
    for k, v in fault.items():
        assert getattr(C.model_config(c), k) == v
