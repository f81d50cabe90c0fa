"""Per-layer counts read from a fixed plan, and the traffic generator."""
import numpy as np

from bench.harness import cell as C, traffic
from bench.reference import dense_decoder


def reader(name):
    return C.reader(name)


def ctx(steps, chips=2, S=8, **kw):
    base = dict(run={}, chips=chips, peaks=None, setup_s=1.0, window_s=2.0,
                steps=steps, S=S, hbm_peak_bytes=3e9, trace=None,
                step_flops=dense_decoder.step_flops)
    base.update(kw)
    return C.Context(**base)


def test_pad_token_share_on_a_fixed_plan():
    # two devices, S = 8: step 1 packs 5 + 3 tokens on one device and 4 on
    # the other in M = 1 (12 of 16 slots); step 2 has M = 2 (10 of 32)
    steps = [C.StepRecord(1, [5, 3, 4], 0.01, 1.0),
             C.StepRecord(2, [6, 4], 0.03, 1.0)]
    share = reader("pad_token_share")(ctx(steps))
    assert np.isclose(share, 100 * (1 - 22 / 48))


def test_host_counts():
    steps = [C.StepRecord(1, [5, 3, 4], 0.01, 1.0),
             C.StepRecord(2, [6, 4], 0.03, 1.0)]
    c = ctx(steps)
    assert np.isclose(reader("host_prep_ms")(c), 20.0)
    assert np.isclose(reader("tokens_per_s")(c), 22 / 2.0)
    assert reader("mfu")(c) is None  # no published peak off a chip
    assert reader("device_idle_share")(c) is None  # no trace
    assert np.isclose(reader("hbm_peak_gb")(c), 3.0)


def test_mfu_counts_model_flops_only():
    run = {"hidden_size": 4, "intermediate_size": 8, "num_attention_heads": 2,
           "num_key_value_heads": 1, "head_dim": 2, "num_hidden_layers": 1,
           "vocab_size": 16}
    steps = [C.StepRecord(1, [3], 0.0, 1.0)]
    c = ctx(steps, run=run, chips=1,
            peaks={"bf16_flops": 1e3}, window_s=1.0)
    params = 4 * 4 + 2 * 4 * 2 + 4 * 4 + 3 * 4 * 8 + 4 * 16
    model = 6 * params * 3 + 12 * 2 * 2 * 6
    assert np.isclose(reader("mfu")(c), 100 * model / 1e3)


def test_traffic_same_seed_same_inputs_and_same_work_across_seeds():
    mix = traffic.load("sft-longalign-1k")
    a = traffic.steps(mix, 1, 2 ** 31 + 5, 151_936)
    b = traffic.steps(mix, 1, 2 ** 31 + 5, 151_936)
    c = traffic.steps(mix, 1, 7, 151_936)
    assert [s.index for s in a] == [s.index for s in b]
    assert all(np.array_equal(x, y) for s, t in zip(a, b)
               for x, y in zip(s.samples, t.samples))
    # another seed: the same compositions in another order
    assert sorted(map(tuple, (s.lengths for s in a))) == \
        sorted(map(tuple, (s.lengths for s in c)))
    assert [s.index for s in a] != [s.index for s in c]
    lens = np.concatenate([s.lengths for s in a])
    assert lens.max() <= 1024 and lens.min() >= 32
    assert all(t.min() >= 1 and t.max() < 151_936 for s in a for t in s.samples)
