"""The fused sweep kernels compile for a TPU v5e chip that is described,
not attached: Mosaic refuses here what it would refuse on the chip
(unaligned slices, unsupported layouts, VMEM overflow).

Every compile happens inside a test: the topology is described in a
module-scoped fixture, so importing this file touches no TPU library.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import engine
from repro.kernels import fused_sweep as F
from repro.kernels import ops

# potts-20x20 (n=400, D=10) padded as kernels.ops pads it: Np=512, Dp=128,
# S=16 sub-steps (Sp=128 lanes); draw widths: mgpmh's capacity 201 -> 256,
# the global minibatches at 128 lanes
N, D, S, C = 400, 10, 16, 64
DP, SP, S8 = 128, 128, 16
KP, K2P = 256, 128


@pytest.fixture(scope="module")
def topo():
    import os
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)


def _kernel_call(name, sds, n=N, kp=KP, k2p=K2P):
    """(kernel, args, kwargs, (vmem-model kernel, draw dims)) at ``n``
    sites, ``kp`` proposal and ``k2p`` global-minibatch draw lanes."""
    f32, i32 = jnp.float32, jnp.int32
    NP, KP, K2P = ops._sweep_pads(C, n, S, D)[1], kp, k2p
    x, tab, tabi = sds((C, NP), i32), sds((NP, NP), f32), sds((NP, NP), i32)
    node, nodei = sds((8, NP), f32), sds((8, NP), i32)
    lane, lanef = sds((C, SP), i32), sds((C, SP), f32)
    gum, cache, seed = sds((C, S8, DP), f32), sds((C, DP), f32), sds((1,), i32)
    strm = lambda L: sds((C, S8, L), f32)
    cand = sds((C, S * D, K2P), f32)
    common = dict(n=n, D=D, S=S)
    calls = {
        "gibbs": (F.gibbs_sweep_pallas, (x, tab, lane, gum),
                  dict(interpret=False), ("gibbs", {})),
        "mgpmh": (F.mgpmh_sweep_pallas,
                  (x, tab, tab, tabi, lane, lane, strm(KP), strm(KP), gum,
                   lanef), dict(scale=0.05, interpret=False),
                  ("mgpmh", dict(K=KP))),
        "mgpmh_rng": (F.mgpmh_sweep_pallas_rng,
                      (x, tab, tab, tabi, lane, lane, seed),
                      dict(Kp=KP, Dp=DP, scale=0.05), ("mgpmh", dict(K=KP))),
        "min_gibbs": (F.min_gibbs_sweep_pallas,
                      (x, node, nodei, tab, tabi, lane,
                       sds((C, S8, DP), i32), cand, cand, cand, cand, gum,
                       cache), dict(lscale=0.02, interpret=False),
                      ("min-gibbs", dict(K=K2P))),
        "min_gibbs_rng": (F.min_gibbs_sweep_pallas_rng,
                          (x, node, nodei, tab, tabi, lane,
                           sds((C, S8, DP), i32), cache, seed),
                          dict(Kp=K2P, Dp=DP, lscale=0.02),
                          ("min-gibbs", dict(K=K2P))),
        "double_min": (F.double_min_sweep_pallas,
                       (x, tab, tabi, node, nodei, lane, lane, strm(KP),
                        strm(KP), gum, lane, strm(K2P), strm(K2P),
                        strm(K2P), strm(K2P), lanef, cache),
                       dict(scale1=0.05, lscale2=0.02, interpret=False),
                       ("doublemin", dict(K=KP, K2=K2P))),
        "double_min_rng": (F.double_min_sweep_pallas_rng,
                           (x, tab, tabi, node, nodei, lane, lane, lane,
                            cache, seed),
                           dict(K1p=KP, K2p=K2P, Dp=DP, scale1=0.05,
                                lscale2=0.02),
                           ("doublemin", dict(K=KP, K2=K2P))),
    }
    fn, args, kw, model = calls[name]
    return fn, args, {**common, **kw}, model


def _estimate(name, **shape):
    """The engine's VMEM estimate for kernel ``name`` at ``shape``."""
    _, _, kw, (kernel, dims) = _kernel_call(name, lambda *a: None, **shape)
    return ops.sweep_vmem_bytes(kernel, n=kw["n"], D=D, S=S, **dims)


def _compile_for_v5e(topo, name, **shape):
    """Compile kernel ``name`` at ``shape`` for one v5e chip, as a Mosaic
    custom call; returns the scoped VMEM Mosaic allocated."""
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    fn, args, kw, _ = _kernel_call(name, sds, **shape)
    text = fn.lower(*args, **kw).compile().as_text()
    assert "tpu_custom_call" in text
    return int(re.search(r'"used_scoped_memory_configs":\[\{[^}]*'
                         r'"size":"(\d+)"', text).group(1))


@pytest.mark.parametrize("name", [
    "gibbs", "mgpmh", "mgpmh_rng", "min_gibbs", "min_gibbs_rng",
    "double_min", "double_min_rng"])
def test_fused_kernel_compiles_for_v5e(topo, name):
    """Each host-rng kernel and each in-kernel-PRNG variant compiles for
    one v5e chip at potts-20x20 shapes, as a Mosaic custom call, within
    the VMEM the engine's estimate grants it."""
    assert 0 < _compile_for_v5e(topo, name) <= _estimate(name)


# the largest shape along one axis that the estimate admits under the
# limit: gibbs at Np 2432; at potts-20x20, mgpmh at 1024 proposal lanes,
# min-gibbs at 512 pair lanes (lam 361) and doublemin at 384 second-batch
# lanes (lam2 252).  One more 128-step along that axis is refused.
@pytest.mark.parametrize("name,axis,size", [
    ("gibbs", "n", 2432), ("mgpmh", "kp", 1024),
    ("min_gibbs", "k2p", 512), ("double_min", "k2p", 384)])
def test_fused_kernel_compiles_at_its_vmem_limit(topo, name, axis, size):
    """At the largest shape ``engine.make`` lets through, each host-rng
    kernel compiles for v5e, and Mosaic allocates no more VMEM than the
    estimate the engine's refusal rests on."""
    fits = _estimate(name, **{axis: size})
    assert fits <= F.VMEM_LIMIT_BYTES < _estimate(name, **{axis: size + 128})
    assert 0 < _compile_for_v5e(topo, name, **{axis: size}) <= fits


def test_pallas_engine_beyond_vmem_raises_clear_error(monkeypatch):
    """Past the VMEM limit ``engine.make(backend='pallas')`` raises an
    error naming the bytes before any kernel is built, and on a TPU
    ``backend='auto'`` picks jnp there instead of a kernel that cannot
    compile (and pallas where the kernel fits)."""
    g = engine.make_workload("potts-20x20").graph
    # min-gibbs's default lam (16384, capacity 17188) outgrows VMEM
    with pytest.raises(ops.KernelTooLarge, match=r"needs about \d+ bytes "
                       r"of VMEM .* limit of \d+ bytes"):
        engine.make("min-gibbs", g, sweep=S, backend="pallas")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    auto = engine.make("min-gibbs", g, sweep=S, backend="auto")
    assert auto.describe()["backend"] == "jnp"
    fits = engine.make("min-gibbs", g, sweep=S, backend="auto", lam=147.0)
    assert fits.describe()["backend"] == "pallas"
    assert fits.describe()["interpret"] is False
