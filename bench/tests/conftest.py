"""CPU tests of the benchmark: four virtual devices, tiny shapes.

Run from the repository's root:  python -m pytest -q bench/tests
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

import copy  # noqa: E402

import pytest  # noqa: E402

TINY_RUN = {"hidden_size": 64, "intermediate_size": 128,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 256,
            "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
            "tie_word_embeddings": True}


# workload -> (configuration file, chips); the four-chip cell's files are
# kept for a later PR to list in BENCHMARK.json
CELLS = {"qwen1.5b-sft-longalign-1chip": ("qwen-1.5b-d8", 1),
         "phi3m-sft-longalign-1chip": ("phi3-medium-14b-d1", 1),
         "qwen1.5b-sft-longalign-4chip": ("qwen-1.5b-d12-fsdp4", 4)}


@pytest.fixture
def tiny_cell():
    """A cell of the benchmark's own files, cut to CPU size: the real
    configuration's options and limits, tiny widths and a 64-token
    microbatch."""
    from bench.harness import cell as C

    def make(workload="qwen1.5b-sft-longalign-1chip", steps_per_cycle=4,
             **run):
        config, chips = CELLS[workload]
        c = C.cell_from_files(workload, ROOT / "bench" / "configs"
                              / f"{config}.json", "sft-longalign-1k", chips)
        c = copy.deepcopy(c)
        c.config["run"] = dict(TINY_RUN, **run)
        c.mix["lengths"] = dict(c.mix["lengths"], rescale_to=64, min_len=4)
        c.mix["microbatch_tokens"] = 64
        c.mix["samples_per_device"] = 4
        c.mix["steps_per_cycle"] = steps_per_cycle
        return c

    return make
