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


def test_plane_get_compiles_on_four_chips(topo):
    """One read-only plane GET on a 4-device `kv` mesh: every shard runs
    the fused kernel over its own 2^21-row pool (32 GiB in all)."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from pmdfc_tpu.parallel import partitioning as pt
    from pmdfc_tpu.parallel import shard

    cfg = _cfg()
    mesh = Mesh(np.asarray(topo.devices[:4]), (shard.AXIS,))
    rules = pt.rules_for_mesh(mesh, None)
    specs = pt.state_specs(cfg, rules)
    one = jax.eval_shape(lambda: kv_mod.init(cfg))
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct((4, *a.shape), a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        one, specs)
    keys = jax.ShapeDtypeStruct((4 * W, 2), jnp.uint32,
                                sharding=NamedSharding(mesh, P(shard.AXIS)))
    fn = jax.jit(shard._shard_map(
        partial(shard._plane_get_ro_body, cfg, 4, True), mesh=mesh,
        in_specs=(specs, P(shard.AXIS)),
        out_specs=(P(shard.AXIS), P(shard.AXIS), P(shard.AXIS))))
    c = fn.lower(state, keys).compile()
    assert "tpu_custom_call" in c.as_text()
    _fits(c)  # per device: one shard's state
