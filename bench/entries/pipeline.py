"""Feature table: `pipeline.pipeline(x, grouping, metric, n_perms, key)`
with defaults.

The table of whole-number counts is made on the device from the seed and
stays resident; each test runs the program's whole features -> p-value
path. After the window the reference builds the exact distance matrix of
the same table with the benchmark's own code and sums over it.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from bench import data, reference


@dataclasses.dataclass
class State:
    x: object
    grouping: object
    perm_key: object
    rng: np.random.Generator
    config: dict
    metric: str
    n_perms: int


def setup(config: dict, traffic: dict, seed: int) -> State:
    data_key, perm_key, rng = data.seeds(seed)
    x, grouping = data.counts(
        data_key, n=config["n"], d=config["d"], n_groups=config["n_groups"],
        density=config["density"], scale=config["scale"],
        effect=config["effect"])
    return State(x=x.block_until_ready(), grouping=grouping,
                 perm_key=perm_key, rng=rng, config=config,
                 metric=traffic["metric"], n_perms=int(traffic["n_perms"]))


def run_test(state: State, t: int):
    from repro import pipeline
    res = pipeline.pipeline(state.x, state.grouping, metric=state.metric,
                            n_perms=state.n_perms,
                            key=data.test_key(state.perm_key, t))
    f = np.asarray(res.f_perms, np.float64)
    return (reference.Answer(test=t, f=f, p=float(res.p_value),
                             s_t=float(res.s_t)), res.plan)


def reference_matrix(state: State):
    """The exact distance matrix of the table, made after the window."""
    return data.distances(state.x, metric=state.metric)
