"""Matrix larger than one chip: `core.distributed.permanova_distributed(
mesh, dm, grouping, n_perms, key, impl="auto")`, as the launcher's
`--distributed` calls it.

D is made in set-up from the seed, born row-sharded over the 'model' axis
of a ('data', 'model') = (1, model_ways) mesh by the benchmark's own
exact builder (bench/sharded.py), and stays resident: each chip holds
its rows of D and no chip holds all of it. Every test runs the whole
sweep over all chips. The reference streams the same sharded D shard by
shard.

Each test's call of the program runs inside the host span CALL.
host.idle_ms counts a device-idle gap as the program's when the
innermost span open over it is not the benchmark's (bench.*). This path
of the program may open no spans of its own (its eager shard_map did
not), and its gaps would then all fall to bench.test; CALL names them as
the program's whole call, and the program's own engine.dist.* spans,
where they exist, name its phases inside it. Nothing but the call runs
in CALL.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from bench import data, reference, sharded

CALL = "engine.dist.call"


@dataclasses.dataclass
class State:
    dm: object
    grouping: object
    perm_key: object
    rng: np.random.Generator
    config: dict
    n_perms: int
    mesh: object


def setup(config: dict, traffic: dict, seed: int) -> State:
    data_key, perm_key, rng = data.seeds(seed)
    x, grouping = data.counts(
        data_key, n=config["n"], d=config["d"], n_groups=config["n_groups"],
        density=config["density"], scale=config["scale"],
        effect=config["effect"])
    mesh = sharded.mesh(int(config["model_ways"]))
    dm = sharded.distances(x, mesh=mesh,
                           metric=config["metric"]).block_until_ready()
    return State(dm=dm, grouping=grouping, perm_key=perm_key, rng=rng,
                 config=config, n_perms=int(traffic["n_perms"]), mesh=mesh)


def run_test(state: State, t: int):
    import jax
    from repro.core import distributed
    key = data.test_key(state.perm_key, t)
    with jax.profiler.TraceAnnotation(CALL):
        res = distributed.permanova_distributed(
            state.mesh, state.dm, state.grouping, n_perms=state.n_perms,
            key=key, impl="auto")
    f = np.asarray(res.f_perms, np.float64)
    return (reference.Answer(test=t, f=f, p=float(res.p_value),
                             s_t=float(res.s_t)),
            res.plan or f"permanova_distributed over {dict(state.mesh.shape)}")


def reference_matrix(state: State):
    """The D the reference sums over: the sharded input itself."""
    return state.dm
