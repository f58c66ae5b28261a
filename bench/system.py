"""The system under test, built as a cell's files say: the program's graph
from the configuration, its engine from the workload, chain keys from
the seed.  Nothing here is timed or compared; it only calls the
program's own constructors."""
from __future__ import annotations

import numpy as np


def graph(config: dict):
    """The program's graph of ``config``: its ``program`` entry names a
    constructor of ``repro.core.factor_graph`` and its arguments."""
    from repro.core import factor_graph
    prog = config["program"]
    return getattr(factor_graph, prog["graph"])(**prog["args"])


def engine(work: dict, g):
    """``engine.make`` with the workload's engine, backend and sweep; a
    dist backend gets a ``(data, model)`` mesh of the workload's shape."""
    import jax
    from repro.core import engine as engine_lib
    mesh = None
    if work["backend"] == "dist":
        from repro.launch.mesh import make_device_mesh
        mesh = make_device_mesh(tuple(work["mesh"]), ("data", "model"),
                                jax.devices())
    return engine_lib.make(work["engine"], g, sweep=work["sweep"],
                           backend=work["backend"], mesh=mesh,
                           **work.get("params", {}))


def key(seed: int, *salt: int):
    """A JAX key (uint32[2]) derived from any whole-number seed, however
    large, and optional salts."""
    import jax.numpy as jnp
    words = np.random.SeedSequence([int(seed), *salt]).generate_state(
        2, dtype=np.uint32)
    return jnp.asarray(words)


def rng(seed: int, *salt: int) -> np.random.Generator:
    """A host generator derived from the seed (traffic, samples)."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *salt]))


def peak_bytes() -> int:
    """Peak bytes in use on the fullest device so far (0 where the
    backend keeps no count)."""
    import jax
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return int(max(s.get("peak_bytes_in_use", 0) for s in stats))
