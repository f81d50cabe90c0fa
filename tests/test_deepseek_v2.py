"""DeepSeek-V2-Lite on the SFT path, at a CPU size, against the plain
reference of the benchmark (``bench/reference/mla_moe.py``): latent
attention with YaRN rope, a leading dense layer, and an expert layer that
holds a share of the routed experts, dropless.  Seeded weights from the
reference's own ``init_params``, which the program takes as they are."""
import dataclasses
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench.reference import common, mla_moe  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import gspmd  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models import moe as moe_mod  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.optim import AdamWConfig, adamw_init  # noqa: E402

# the configuration file's keys at a CPU size: 1 dense + 2 expert layers,
# 4 of 8 experts held, 3 a token
RUN = dict(json.loads((ROOT / "bench" / "configs" /
                       "deepseek-v2-lite-ep8-d5.json").read_text())["run"],
           hidden_size=64, intermediate_size=96, num_attention_heads=4,
           num_key_value_heads=4, num_hidden_layers=3, vocab_size=128,
           kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
           v_head_dim=8, moe_intermediate_size=32, n_routed_experts=4,
           router_outputs=8, num_experts_per_tok=3)
S = 32


def program_config(run=RUN, **kw):
    """The registry entry at the run's sizes, as the benchmark builds it;
    ``kw``: other ModelConfig fields."""
    sizes = dict(
        num_layers=run["num_hidden_layers"],
        d_model=run["hidden_size"], num_heads=run["num_attention_heads"],
        num_kv_heads=run["num_key_value_heads"],
        d_ff=run["intermediate_size"], vocab_size=run["vocab_size"],
        kv_lora_rank=run["kv_lora_rank"],
        qk_nope_head_dim=run["qk_nope_head_dim"],
        qk_rope_head_dim=run["qk_rope_head_dim"],
        v_head_dim=run["v_head_dim"],
        moe_d_ff=run["moe_intermediate_size"],
        shared_expert_d_ff=(run["n_shared_experts"]
                            * run["moe_intermediate_size"]),
        num_experts=run["router_outputs"],
        experts_held=run["n_routed_experts"],
        experts_per_token=run["num_experts_per_tok"])
    return dataclasses.replace(get_config("deepseek-v2-lite"),
                               **{**sizes, **kw})


def weights(run=RUN, seed=2 ** 31 + 11):
    shape = mla_moe.Shape.from_config(run)
    return shape, mla_moe.init_params(shape, common.seed_key_data(seed))


def samples(lengths, vocab, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in lengths]


def packed(rows_of_samples, S=S):
    """One packed row per list of samples: the program's batch layout."""
    keys = ("tokens", "targets", "positions", "segment_ids", "loss_mask")
    out = {k: np.zeros((len(rows_of_samples), S),
                       np.float32 if k == "loss_mask" else np.int32)
           for k in keys}
    out["segment_ids"][:] = -1
    for r, row in enumerate(rows_of_samples):
        at = 0
        for seg, t in enumerate(row):
            n = len(t)
            sl = slice(at, at + n)
            out["tokens"][r, sl] = t
            out["targets"][r, at:at + n - 1] = t[1:]
            out["positions"][r, sl] = np.arange(n)
            out["segment_ids"][r, sl] = seg
            out["loss_mask"][r, at:at + n - 1] = 1.0
            at += n
    return {k: jnp.asarray(v) for k, v in out.items()}


def _rel(a, b):
    return float(jnp.linalg.norm((a - b).ravel())
                 / max(float(jnp.linalg.norm(b.ravel())), 1e-30))


# (a) ------------------------------------------------------------------------
@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_every_gradient_match_the_reference(remat):
    cfg = program_config()
    shape, params = weights()
    rows = [samples([5, 9, 11], RUN["vocab_size"], 1),
            samples([17, 6], RUN["vocab_size"], 2)]
    batch = packed(rows)

    def prog(p):
        return T.loss(cfg, p, batch, remat=remat, reduction="sum")[0]

    def ref(p):
        flat = [t for row in rows for t in row]
        tok, tgt, mask = common.rows(flat, S)
        return mla_moe.nll_sum(shape, p, tok, tgt, mask)

    lp, gp = jax.value_and_grad(prog)(params)
    lr, gr = jax.value_and_grad(ref)(params)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    flat_p = jax.tree_util.tree_flatten_with_path(gp)[0]
    flat_r = dict(jax.tree_util.tree_flatten_with_path(gr)[0])
    assert len(flat_p) == len(flat_r)
    for path, g in flat_p:
        assert _rel(g, flat_r[path]) < 1e-4, jax.tree_util.keystr(path)
    # the balance loss is in both: with alpha 0 the sums move together
    no_aux = dataclasses.replace(cfg, router_aux_coef=0.0)
    shape0 = dataclasses.replace(shape, aux_alpha=0.0)
    d_prog = lp - T.loss(no_aux, params, batch, reduction="sum")[0]
    flat = [t for row in rows for t in row]
    d_ref = lr - mla_moe.nll_sum(shape0, params, *common.rows(flat, S))
    assert float(d_prog) > 0
    assert float(d_prog) == pytest.approx(float(d_ref), rel=1e-4)


# (b) ------------------------------------------------------------------------
def test_the_shares_add_up_to_the_uncut_layer():
    """Eight experts held as four shares of two: the shares' routed parts,
    with the shared experts counted once, give the uncut reference's
    layer."""
    run = dict(RUN, n_routed_experts=8)
    shape, params = weights(run)
    lp = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(3), (2, S, RUN["hidden_size"]))
    real = jnp.ones((2, S))
    routed, _ = mla_moe._routed(shape, x, lp["moe"], real)
    whole = routed + mla_moe._swiglu_apply(x, lp["shared_mlp"])
    cfg = program_config(run, experts_held=2)
    total = L.mlp_apply(cfg, lp["shared_mlp"], x)
    for j in range(4):
        held = [2 * j, 2 * j + 1]
        order = held + [e for e in range(8) if e not in held]
        share = {"router": lp["moe"]["router"][:, jnp.asarray(order)],
                 **{n: lp["moe"][n][2 * j:2 * j + 2]
                    for n in ("w_up", "w_down", "w_gate")}}
        out, _, _ = moe_mod.moe_apply_grouped(cfg, share, x)
        total = total + out
    assert _rel(total, whole) < 1e-5


# (c) ------------------------------------------------------------------------
def test_dropless_keeps_every_token_on_one_expert():
    """A router of zeros sends every token to expert 0 (k = 1): the
    dropless layer computes all of them; a capacity of 1.0 would keep a
    quarter."""
    run = dict(RUN, num_experts_per_tok=1)
    shape, params = weights(run)
    lp = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    p = dict(lp["moe"], router=jnp.zeros_like(lp["moe"]["router"]))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, S, RUN["hidden_size"]))
    want, _ = mla_moe._routed(shape, x, p, jnp.ones((2, S)))
    cfg = program_config(run)
    out, _, pairs = moe_mod.moe_apply_grouped(cfg, p, x)
    assert float(pairs) == 2 * S
    assert _rel(out, want) < 1e-5
    ffn = {n: p[n][0] for n in ("w_up", "w_down", "w_gate")}
    assert _rel(out, mla_moe._swiglu_apply(x, ffn) / 8) < 1e-5
    capped = dataclasses.replace(cfg, moe_capacity_factor=1.0)
    out_c, _, pairs_c = moe_mod.moe_apply_grouped(capped, p, x)
    cap = math.ceil(2 * S * 1.0 * 1 / 8)
    assert float(pairs_c) == cap
    kept = jnp.zeros(2 * S).at[:cap].set(1.0).reshape(2, S, 1)
    assert _rel(out_c, out * kept) < 1e-6


# (d) ------------------------------------------------------------------------
def test_yarn_frequencies_and_softmax_scale():
    cfg = get_config("deepseek-v2-lite")
    inv, mscale = L.yarn_freqs(cfg, cfg.qk_rope_head_dim)
    base = L.rope_freqs(64, 10_000.0)
    np.testing.assert_allclose(inv[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], base[23:] / 40, rtol=1e-6)
    mid = np.asarray(inv[11:23])
    assert np.all(mid < np.asarray(base[11:23]))
    assert np.all(mid > np.asarray(base[11:23]) / 40)
    assert mscale == 1.0
    assert L.mla_softmax_scale(cfg) == pytest.approx(0.11472, abs=5e-6)
    ref = mla_moe.Shape.from_config(json.loads(
        (ROOT / "bench" / "configs" / "deepseek-v2-lite-ep8-d5.json")
        .read_text())["run"])
    ref_inv, ref_mscale = mla_moe.yarn_inv_freq(ref)
    np.testing.assert_allclose(inv, ref_inv, rtol=1e-6)
    assert ref_mscale == mscale
    assert mla_moe.softmax_scale(ref) == pytest.approx(
        L.mla_softmax_scale(cfg), rel=1e-12)


# (e) ------------------------------------------------------------------------
@pytest.mark.parametrize("block_kv", [8, 32])
def test_blockwise_attention_with_a_narrower_v(block_kv):
    B, S_, H, dq, dv = 2, 32, 4, 24, 8
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(k1, (B, S_, H, dq))
    k = jax.random.normal(k2, (B, S_, H, dq))
    v = jax.random.normal(k3, (B, S_, H, dv))
    seg = jnp.asarray([[0] * 20 + [1] * 12, [0] * 32])
    out = L.blockwise_attention(q, k, v, q_segment_ids=seg,
                                kv_segment_ids=seg, block_kv=block_kv,
                                scale=0.3)
    assert out.shape == (B, S_, H, dv)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 0.3
    pos = jnp.arange(S_)
    ok = (pos[:, None] >= pos[None, :])[None] & (
        seg[:, :, None] == seg[:, None, :])
    s = jnp.where(ok[:, None], s, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(out, want, atol=2e-5)


# (f) ------------------------------------------------------------------------
def test_four_device_odc_step_matches_one_device():
    cfg = program_config()
    shape, params = weights()
    lens = [[5, 9], [12], [7, 7, 3], [20], [4, 4], [9, 10], [30], [2, 6]]
    rows = [samples(l, RUN["vocab_size"], i) for i, l in enumerate(lens)]
    batch = packed(rows)
    batch = {k: v.reshape(2, 4, S) for k, v in batch.items()}  # (M, W, S)
    opt = AdamWConfig(lr=1e-3)
    outs = []
    for n in (1, 4):
        mesh = make_host_mesh(data=n, model=1)
        gcfg = gspmd.GSPMDConfig(rules=gspmd.ShardingRules(),
                                 schedule="minibatch", comm="odc",
                                 block_kv=16)
        step = gspmd.jit_train_step(cfg, mesh, gcfg, opt)
        p_sh, o_sh = gspmd.train_state_shardings(cfg, mesh, gcfg)
        # host copies: the step donates the state it is given
        state = jax.device_put(jax.tree.map(np.asarray, (
            params, adamw_init(params))), (p_sh, o_sh))
        b = jax.device_put(batch, gspmd.train_batch_shardings(batch, mesh,
                                                              gcfg))
        with mesh:
            _, new_opt, metrics = step(*state, b)
        # the first moment after one step: (1 - b1) x the clipped gradient
        outs.append((jax.device_get(new_opt["m"]), jax.device_get(metrics)))
    (g1, m1), (g4, m4) = outs
    assert float(m4["loss"]) == pytest.approx(float(m1["loss"]), rel=1e-5)
    assert float(m1["tokens"]) == float(m4["tokens"]) == float(
        sum(len(t) - 1 for row in rows for t in row))
    assert float(m1["moe_pairs_held"]) == float(m4["moe_pairs_held"]) > 0
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g4)):
        assert _rel(jnp.asarray(b), jnp.asarray(a)) < 1e-5


# (g) ------------------------------------------------------------------------
def test_launch_train_streams_the_pairs_held(tmp_path):
    from repro.launch import train as train_mod

    path = tmp_path / "metrics.jsonl"
    rc = train_mod.main(["--arch", "deepseek-v2-lite", "--reduced",
                         "--steps", "2", "--data-axis", "2",
                         "--minibatch-per-device", "2", "--max-tokens", "64",
                         "--max-len", "48", "--metrics", str(path)])
    assert rc == 0
    lines = [json.loads(x) for x in path.read_text().splitlines()]
    steps = [x for x in lines if "metrics" in x]
    assert len(steps) == 2
    counters = [{r["name"]: r["value"] for r in s["metrics"]
                 if r["kind"] == "counter"} for s in steps]
    for c in counters:
        assert c["moe.pairs_held"] > 0 and c["data.token_slots"] > 0
    # cumulative: the second step adds its own pairs
    assert counters[1]["moe.pairs_held"] > counters[0]["moe.pairs_held"]
    cfg = get_config("deepseek-v2-lite")
    from repro.models.config import reduced
    r = reduced(cfg)
    # every slot routes k pairs in each expert layer; all are held here
    most = r.num_moe_layers * r.experts_per_token
    assert counters[1]["moe.pairs_held"] <= most * counters[1][
        "data.token_slots"]


# (h) ------------------------------------------------------------------------
def test_pairs_held_is_a_hand_count_of_a_planted_routing():
    """Token n carries feature c(n); the router sends feature c to experts
    a(c) and b(c).  The pairs held are those whose expert is below
    experts_held."""
    run = dict(RUN, num_experts_per_tok=2)
    cfg = program_config(run)
    d, E = RUN["hidden_size"], 8
    rng = np.random.default_rng(8)
    a = rng.integers(0, E, d)
    b = (a + rng.integers(1, E, d)) % E
    router = np.zeros((d, E), np.float32)
    router[np.arange(d), a] = 10.0
    router[np.arange(d), b] = 5.0
    c = rng.integers(0, d, (2, S))
    x = jax.nn.one_hot(jnp.asarray(c), d)
    _, params = weights(run)
    p = dict(jax.tree.map(lambda t: t[0], params["layers"]["moe"]["moe"]),
             router=jnp.asarray(router))
    _, _, pairs = moe_mod.moe_apply_grouped(cfg, p, x)
    held = cfg.resolved_experts_held
    hand = int(np.sum(a[c] < held) + np.sum(b[c] < held))
    assert float(pairs) == hand
    # through the model: summed over the expert layers of a microbatch
    _, params = weights(run)
    batch = packed([samples([12, 9], RUN["vocab_size"], 9)])
    _, metrics = T.loss(cfg, params, batch, reduction="sum")
    assert 0 < float(metrics["moe_pairs_held"]) <= 2 * S * 2


def test_decode_through_the_latent_cache_matches_the_full_forward():
    cfg = program_config()
    _, params = weights()
    B, n = 2, 16
    tok = jax.random.randint(jax.random.PRNGKey(9), (B, n), 0,
                             RUN["vocab_size"])
    pos = jnp.arange(n)[None].repeat(B, 0)
    full, _, _ = T.apply(cfg, params, {"tokens": tok, "positions": pos})
    cache = T.init_cache(cfg, B, n)
    assert set(cache["lead"]) == {"c", "k_rope"}
    assert cache["moe"]["c"].shape == (2, B, n, RUN["kv_lora_rank"])
    _, _, cache = T.apply(cfg, params, {"tokens": tok[:, :-1],
                                        "positions": pos[:, :-1]},
                          caches=cache, cache_index=0)
    last, _, _ = T.apply(cfg, params, {"tokens": tok[:, -1:],
                                       "positions": pos[:, -1:]},
                         caches=cache, cache_index=n - 1)
    np.testing.assert_allclose(last[:, 0], full[:, -1], atol=2e-4)


def test_parameter_counts_of_the_cut():
    """The held share: 1 dense + 4 expert layers, 8 of 64 experts, 12,800
    vocabulary rows (the benchmark's configuration)."""
    cut = dataclasses.replace(get_config("deepseek-v2-lite"), num_layers=5,
                              vocab_size=12_800, experts_held=8)
    assert cut.attn_params_per_layer() == 13_763_072
    assert cut.num_params() == (
        2 * 12_800 * 2048 + 5 * (13_763_072 + 2 * 2048)
        + 3 * 2048 * 10_944 + 4 * (2048 * 64 + 3 * 2048 * 2816
                                   + 8 * 3 * 2048 * 1408))
    params = T.init_params(dataclasses.replace(
        cut, num_layers=2, d_model=64, vocab_size=64, d_ff=32, moe_d_ff=16,
        shared_expert_d_ff=32, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8), jax.random.PRNGKey(0))
    assert params["layers"]["moe"]["moe"]["w_up"].shape == (1, 8, 64, 16)
    assert params["layers"]["moe"]["moe"]["router"].shape == (1, 64, 64)
    # a token meets 6 * 8 / 64 = 0.75 of the held experts on average
    expert = 3 * 2048 * 1408
    assert cut.num_params() - cut.num_active_params() == round(
        4 * expert * (8 - 0.75))


def test_rows_past_the_held_groups_never_reach_the_result(monkeypatch):
    """A chip's grouped-matmul kernel leaves the rows past the held groups
    unwritten, in its result and in its input gradient (the CPU writes
    zeros).  Filled with NaN here, and passed on as they come, they must
    change neither the layer nor any gradient."""
    real = moe_mod._gmm

    def unwritten(x, w, sizes, transpose_rhs=False):
        y = real(x, w, sizes, transpose_rhs)
        rows = jnp.arange(y.shape[0]) < jnp.sum(sizes)
        return jnp.where(rows[:, None], y, jnp.nan)

    cfg = program_config()
    shape, params = weights()
    batch = packed([samples([5, 9, 11], RUN["vocab_size"], 1),
                    samples([17, 6], RUN["vocab_size"], 2)])

    def grads():
        return jax.value_and_grad(
            lambda p: T.loss(cfg, p, batch, reduction="sum")[0])(params)

    want_l, want_g = grads()
    monkeypatch.setattr(moe_mod, "_gmm", unwritten)
    got_l, got_g = grads()
    assert bool(jnp.isfinite(got_l)) and float(got_l) == float(want_l)
    for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_array_equal(a, b)


def test_the_chip_kernels_match_ragged_dot(monkeypatch):
    """The megablox kernels the chip runs (interpreted here) and
    ``jax.lax.ragged_dot`` give one grouped matmul: result, x's gradient
    and the weights' gradient, rows past the groups zero, an empty group
    included."""
    kx, kw, kg = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(kx, (512, 256), jnp.float32)
    w = jax.random.normal(kw, (4, 256, 384), jnp.float32) / 16
    g = jax.random.normal(kg, (512, 384), jnp.float32)
    sizes = jnp.array([100, 0, 150, 60], jnp.int32)

    def run():
        y, vjp = jax.vjp(lambda x, w: moe_mod.grouped_matmul(x, w, sizes),
                         x, w)
        return (y, *vjp(g))

    want = run()
    monkeypatch.setattr(moe_mod, "_kernel", lambda: True)
    assert moe_mod._tiling(512, 256, 384) == (256, 256, 384)
    got = run()
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
    assert not np.any(np.asarray(got[0][310:]))
    assert not np.any(np.asarray(got[1][310:]))
