"""AOT compiles of the serving programs for a described TPU v5e.

Nothing runs here: each case lowers and compiles a real-size program for a
`v5e:2x2` topology that is described, not attached, so what Mosaic or XLA
would refuse on the chip (unaligned DMA slices, unsupported reductions, a
state that does not fit 16 GiB) fails here at no chip time. The fused GET
kernel is compiled with `interpret=False` by steering `fused._interpret`.

The topology is described inside a module fixture, never at import time: a
worker that loads the TPU library holds it until it exits, so every case
lives in this one file.
"""

from __future__ import annotations

import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pmdfc_tpu import kv as kv_mod
from pmdfc_tpu.config import (BloomConfig, IndexConfig, IndexKind, KVConfig,
                              TierConfig)
from pmdfc_tpu.ops import fused

HBM_BYTES = 16 << 30  # one v5e chip
CAPACITY = 1 << 21    # the chip smoke's pool: 2^21 rows of 4 KiB
W = 4096              # keys per GET batch


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache

    # compiles for a described chip are written to the persistent cache
    # but cannot be read back without one: keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fused, "_interpret", lambda: False)
        yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _cfg(kind=IndexKind.LINEAR, capacity=CAPACITY, tiered=False):
    return KVConfig(index=IndexConfig(kind=kind, capacity=capacity),
                    bloom=BloomConfig(num_bits=1 << 28, num_hashes=4),
                    paged=True, page_words=1024,
                    tier=TierConfig() if tiered else None)


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile(name, cfg, sharding, *extra):
    """Compile the KV wrapper's donated program `name` for one chip."""
    state = _shapes(jax.eval_shape(lambda: kv_mod.init(cfg)), sharding)
    return kv_mod._DON_FNS[name].lower(state, cfg, *extra).compile()


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes
            + m.generated_code_size_in_bytes)
    assert need <= HBM_BYTES, f"needs {need / 2**30:.2f} GiB: {m}"
    return need


@pytest.mark.parametrize("name,kind,capacity,tiered", [
    ("get_fused", IndexKind.LINEAR, CAPACITY, False),
    ("get_fused_lean", IndexKind.LINEAR, CAPACITY, False),
    ("get_fused", IndexKind.LINEAR, CAPACITY, True),
    # cceh allocates 2x the pool rows of its capacity: 2^20 fits one chip
    ("get_fused", IndexKind.CCEH, CAPACITY // 2, False),
])
def test_fused_get_compiles_for_v5e(one_chip, name, kind, capacity, tiered):
    keys = jax.ShapeDtypeStruct((W, 2), jnp.uint32, sharding=one_chip)
    c = _compile(name, _cfg(kind, capacity, tiered), one_chip, keys)
    assert "tpu_custom_call" in c.as_text()
    _fits(c)


@pytest.mark.parametrize("name,width", [("insert", 8192),
                                        ("get_compact", W)])
def test_composed_program_fits_one_chip(one_chip, name, width):
    """The composed insert and hit-compacted GET at capacity 2^21 fit the
    chip only because the state is donated: a copy of the 8 GiB pool
    would not."""
    cfg = _cfg()
    args = [jax.ShapeDtypeStruct((width, 2), jnp.uint32, sharding=one_chip)]
    if name == "insert":
        args.append(jax.ShapeDtypeStruct((width, 1024), jnp.uint32,
                                         sharding=one_chip))
    c = _compile(name, cfg, one_chip, *args)
    m = c.memory_analysis()
    assert m.alias_size_in_bytes >= 8 << 30, m  # the pool is donated
    _fits(c)


def _plane(topo, cfg, n):
    """(mesh, state specs, stacked state shapes, data sharding) of an
    n-device `kv` plane over the described chips."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from pmdfc_tpu.parallel import partitioning as pt
    from pmdfc_tpu.parallel import shard

    mesh = Mesh(np.asarray(topo.devices[:n]), (shard.AXIS,))
    specs = pt.state_specs(cfg, pt.rules_for_mesh(mesh, None))
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct((n, *a.shape), a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        jax.eval_shape(lambda: kv_mod.init(cfg)), specs)
    return mesh, specs, state, NamedSharding(mesh, P(shard.AXIS))


def _plane_get(topo, cfg, n, width, fused_get):
    from jax.sharding import PartitionSpec as P

    from pmdfc_tpu.parallel import shard

    mesh, specs, state, data = _plane(topo, cfg, n)
    keys = jax.ShapeDtypeStruct((n * width, 2), jnp.uint32, sharding=data)
    fn = jax.jit(shard._shard_map(
        partial(shard._plane_get_ro_body, cfg, n, fused_get), mesh=mesh,
        in_specs=(specs, P(shard.AXIS)),
        out_specs=(P(shard.AXIS), P(shard.AXIS), P(shard.AXIS))))
    return fn.lower(state, keys).compile()


def _plane_insert(topo, cfg, n, width):
    from jax.sharding import PartitionSpec as P

    from pmdfc_tpu.parallel import shard

    mesh, specs, state, data = _plane(topo, cfg, n)
    keys = jax.ShapeDtypeStruct((n * width, 2), jnp.uint32, sharding=data)
    pages = jax.ShapeDtypeStruct((n * width, cfg.page_words), jnp.uint32,
                                 sharding=data)
    fn = jax.jit(shard._shard_map(
        partial(shard._plane_insert_body, cfg, n), mesh=mesh,
        in_specs=(specs, P(shard.AXIS), P(shard.AXIS)),
        out_specs=(specs, (P(shard.AXIS), P(shard.AXIS)))),
        donate_argnums=(0,))
    return fn.lower(state, keys, pages).compile()


def test_plane_get_compiles_on_four_chips(topo):
    """One read-only plane GET on a 4-device `kv` mesh: every shard runs
    the fused kernel over its own 2^21-row pool (32 GiB in all)."""
    c = _plane_get(topo, _cfg(), 4, W, True)
    assert "tpu_custom_call" in c.as_text()
    _fits(c)  # per device: one shard's state


def _bloom_ops(hlo: str) -> dict:
    """The instructions of an optimized module that write a whole
    2^28-counter bloom, by kind: `copy` or `fusion` (a scatter)."""
    out: dict = {"copy": [], "fusion": []}
    for m in re.finditer(r"^\s*%(\S+) = s32\[268435456\]\{[^}]*\} (\w+)\(",
                         hlo, re.M):
        if m[2] in out:
            out[m[2]].append(m[1])
    return out


def test_plane_insert_compiles_for_v5e(topo):
    """The one-shard plane insert at the served PUT width (8,192 keys,
    state donated) fits one chip. Its bloom passes: the insert and the
    delete scatter-adds, and at most two whole-bloom copies, which XLA
    makes for the two branch operands of `_bf_delete`'s `lax.cond`."""
    c = _plane_insert(topo, _cfg(), 1, 8192)
    assert c.memory_analysis().alias_size_in_bytes >= 9 << 30
    _fits(c)
    ops = _bloom_ops(c.as_text())
    assert len(ops["fusion"]) == 2, ops
    assert len(ops["copy"]) <= 2, ops


def test_four_chip_cell_programs_compile(topo):
    """The `linear-32g-4chip` cell's plane programs, from its configuration
    file, on a 4-device `kv` mesh: the composed GET (16-slot rows take no
    kernel) at the top of the warmed ladder, 16,384 keys a shard, and the
    donated insert at the served PUT width, 8,192 keys a shard. Each
    device holds one shard's 2^21-row pool and bloom, and fits its chip."""
    import json

    from benchmark.run import kv_config

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "linear-32g-4chip.json")
    with open(path) as f:
        cell = json.load(f)
    cfg = kv_config(cell)
    n = cell["mesh"]["n_shards"]
    assert n == 4 and not fused.supports(cfg)
    get = _plane_get(topo, cfg, n, 16384, False)
    assert "tpu_custom_call" not in get.as_text()
    _fits(get)
    ins = _plane_insert(topo, cfg, n, 8192)
    assert ins.memory_analysis().alias_size_in_bytes >= 9 << 30
    _fits(ins)
