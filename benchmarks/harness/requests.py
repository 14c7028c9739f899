"""The one general request generator: a traffic file's numbers in, a list
of requests out.

Every seed gets the SAME sizes in the SAME order (drawn once from the
file's ``shape_seed``); the seed draws only the token ids. A request of
the rollout mix lives for up to four windows, so with the order on the
seed a window held whatever that order had put in flight, and the rate
swung by +-2.3 % from seed to seed (PERF.md, Findings of PR 24).

Fields of ``traffic["requests"]``: ``count`` (requests in the pool; the
pool is cycled), ``group`` (requests that share one prompt), ``prompt``
and ``output`` (a distribution each: ``{"dist": "uniform", "low", "high"}``
or ``{"dist": "lognormal", "median", "sigma", "low", "high"}``).
"""

from __future__ import annotations

import numpy as np


def draw(spec: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    if spec["dist"] == "uniform":
        x = rng.integers(spec["low"], spec["high"] + 1, n)
    elif spec["dist"] == "lognormal":
        x = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], n))
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    return np.clip(np.rint(x), spec["low"], spec["high"]).astype(np.int64)


def make_pool(spec: dict, vocab: int, seed: int, max_total: int) -> list[dict]:
    """[{"prompt": int32 [P], "new": int, "group": g}], group by group."""
    shape_rng = np.random.default_rng(int(spec.get("shape_seed", 0)))
    group = int(spec.get("group", 1))
    n_groups = int(spec["count"]) // group
    p_len = draw(spec["prompt"], shape_rng, n_groups)
    new = draw(spec["output"], shape_rng, n_groups * group).reshape(n_groups, group)
    new = np.minimum(new, max_total - p_len[:, None])
    rng = np.random.default_rng(int(seed))
    pool = []
    for g in range(n_groups):
        prompt = rng.integers(0, vocab, p_len[g]).astype(np.int32)
        for n in new[g]:
            pool.append({"prompt": prompt, "new": int(n), "group": g})
    return pool
