"""Matrix input: `engine.run(dm, grouping, n_perms, key)` with defaults.

D is made on the device from the seed by the benchmark's own exact
distance builder and stays resident; every test runs the whole s_W sweep
over it. The reference reads the same D.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from bench import data, reference


@dataclasses.dataclass
class State:
    dm: object
    grouping: object
    perm_key: object
    rng: np.random.Generator
    config: dict
    n_perms: int


def setup(config: dict, traffic: dict, seed: int) -> State:
    data_key, perm_key, rng = data.seeds(seed)
    x, grouping = data.counts(
        data_key, n=config["n"], d=config["d"], n_groups=config["n_groups"],
        density=config["density"], scale=config["scale"],
        effect=config["effect"])
    dm = data.distances(x, metric=config["metric"]).block_until_ready()
    return State(dm=dm, grouping=grouping, perm_key=perm_key, rng=rng,
                 config=config, n_perms=int(traffic["n_perms"]))


def run_test(state: State, t: int):
    from repro import engine
    res = engine.run(state.dm, state.grouping, n_perms=state.n_perms,
                     key=data.test_key(state.perm_key, t))
    f = np.asarray(res.f_perms, np.float64)
    return (reference.Answer(test=t, f=f, p=float(res.p_value),
                             s_t=float(res.s_t)), res.plan)


def reference_matrix(state: State):
    """The D the reference sums over: the input itself."""
    return state.dm
