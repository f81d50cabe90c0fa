"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<name>.json`` and draws the samples of every step.

A mix fixes a pool of step compositions (which sample lengths train
together), drawn once from the mix's own ``layout_seed``; every run seed
trains the same compositions in another order, with its own token ids.
So runs of different seeds do the same amount of work and compile the
same shapes, and the seed changes only the values.

Length shapes follow the paper's datasets (Fig. 7): a log-normal body,
an optional uniform long tail, clipped, and rescaled so the shape is
preserved at a smaller cap (the repo's ``repro/data/lengths.py``, copied
here so that the yardstick cannot move).
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import List

import numpy as np

DIR = Path(__file__).resolve().parents[1] / "traffic"


def load(name: str) -> dict:
    return json.loads((DIR / f"{name}.json").read_text())


def sample_lengths(spec: dict, n: int, rng: np.random.RandomState) -> np.ndarray:
    """n lengths of the shape ``spec`` (log-normal ``mu``/``sigma``, a
    ``tail_frac`` of uniform mass on [``tail_lo``, ``max_len``], clipped to
    [``min_len``, ``max_len``]), rescaled to ``rescale_to`` if given."""
    lens = rng.lognormal(spec["mu"], spec["sigma"], size=n)
    if spec.get("tail_frac", 0) > 0:
        t = rng.rand(n) < spec["tail_frac"]
        lens[t] = rng.uniform(spec["tail_lo"], spec["max_len"], size=t.sum())
    lens = np.clip(lens, spec["min_len"], spec["max_len"])
    cap = spec.get("rescale_to", 0)
    if cap and cap != spec["max_len"]:
        lens = np.clip(lens * (cap / spec["max_len"]), spec["min_len"], cap)
    return lens.astype(np.int64)


@dataclasses.dataclass
class Step:
    """One optimizer step's samples (each a 1-D int32 token array)."""

    index: int  # position in the mix's pool of compositions
    samples: List[np.ndarray]

    @property
    def lengths(self) -> List[int]:
        return [len(t) for t in self.samples]


def compositions(mix: dict, world: int) -> np.ndarray:
    """(steps_per_cycle, samples per step) lengths: the mix's fixed pool."""
    per_step = mix["samples_per_device"] * world
    k = mix["steps_per_cycle"]
    rng = np.random.RandomState(mix["layout_seed"])
    lens = sample_lengths(mix["lengths"], k * per_step, rng)
    lens = np.minimum(lens, mix["microbatch_tokens"])
    return lens.reshape(k, per_step)


def steps(mix: dict, world: int, seed: int, vocab: int) -> List[Step]:
    """The seed's cycle of steps: the pool in a seeded order, with token
    ids drawn per sample (Zipf with exponent ``tokens.zipf_a``, clipped to
    the vocabulary)."""
    pool = compositions(mix, world)
    rng = np.random.default_rng(seed % 2 ** 64)
    order = rng.permutation(len(pool))
    a = mix["tokens"]["zipf_a"]
    out = []
    for k in order:
        toks = [np.minimum(rng.zipf(a, size=int(n)), vocab - 1).astype(np.int32)
                for n in pool[k]]
        out.append(Step(int(k), toks))
    return out
