"""Device-fused GET: Pallas probe→gather→digest in ONE kernel.

The composed GET program (`kv._get_core`) is a chain of XLA HLOs — index
row gather, lane match, pool row gather, digest recompute, tier/generation
fold, miss-cause classify — with an HBM-materialized intermediate between
every stage. This module runs the row traffic of the verb as one Pallas
TPU kernel per index family: bucket rows and page rows are DMA'd into
VMEM, and the match and the digest recompute run on VPU lanes there
(HashMem's "move the map into the memory device" argument, applied to the
serving GET).

Kernel anatomy (per `tile` keys of the padded batch, grid = w / tile):

1. **address fold** (vector): murmur3 bucket/window hashes on VPU lanes,
   then one local DMA lands them in SMEM (DMA descriptors index from
   scalar memory). CCEH's directory walk is a scalar loop over the
   SMEM-resident replicated directory.
2. **probe** (DMA ring, depth 8): each key's `[khi|klo|vhi|vlo]` bucket
   row lands in VMEM.
3. **match** (vector): `rowops.match_mask`/`lane_pick` semantics on the
   VMEM-resident rows — found/values/slot per lane, tag split
   (EXTENT/NOPAGE), exactly as the composed program.
4. **gather + digest** (DMA ring + vector): page rows land in the output
   block and the at-rest digest is recomputed in VMEM
   (`pagepool.page_digest`, xor tree-fold).
5. **pre-classify** (vector): every lane leaves with hit / pad / cold /
   extent / parked. The sidecar verdicts — evicted sketch, digest
   compare, tier generation and liveness — are single-word lookups,
   which Mosaic cannot DMA per key (HBM is (8, 128)-tiled), so `get_core`
   finishes them in XLA inside the same jitted program with the composed
   program's own helpers: `misses == Σ causes` holds bit-exactly.

Mosaic DMAs whole (8, 128) tiles only, so each key fetches the aligned
8-row group holding its row and copies the row out of VMEM (see
`_pipeline`): 8x the bytes of the rows themselves.

`get_core` is the drop-in twin of `kv._get_core` (same signature, same
returns, bit-identical outputs and stats deltas); the counting tiered
epilogue (`tier.on_get`) and the recovering reattribution stay composed
XLA *inside the same jitted program* — they are scatter-heavy state
updates, not row traffic. Unsupported configurations (index families
other than linear/cceh, unpaged pools, non-pow2 page widths, row counts
off the 8-row group) silently ride the composed program.

Platform gate: off-TPU the kernel runs in Pallas interpret mode
(conformance/parity only; `resolve()` never *selects* fused off-chip
unless forced with PMDFC_FUSED=on / `KVConfig(fused_get="on")`).
`tests/test_chip_compile.py` compiles it for a described v5e.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pmdfc_tpu import tier as tier_mod
from pmdfc_tpu.config import IndexKind, KVConfig, fused_mode
from pmdfc_tpu.models.cceh import WINDOW_SEED
from pmdfc_tpu.models.rowops import lane_pick, match_mask
from pmdfc_tpu.ops import pagepool
from pmdfc_tpu.ops.pagepool import _FINAL_MIX, _FNV_PRIME, _LANE_SALT
from pmdfc_tpu.utils.hashing import hash_u64
from pmdfc_tpu.utils.keys import is_invalid

# per-lane outcome codes (disjoint by construction; HIT ⟺ final found)
(CAUSE_HIT, CAUSE_PAD, CAUSE_COLD, CAUSE_EVICTED, CAUSE_EXT,
 CAUSE_PARKED, CAUSE_STALE, CAUSE_DIGEST) = range(8)

# mirrored from kv (which imports us lazily — no module cycle); `get_core`
# asserts parity at trace time so drift is impossible to miss
_EXTENT_TAG = 0x80000000  # kv.EXTENT_TAG

_DEPTH = 8  # in-flight DMAs per stream (each stream has its own sem ring)
_SUB = 8    # rows per (8, 128) HBM tile: the unit one DMA may move

FUSED_FAMILIES = (IndexKind.LINEAR, IndexKind.CCEH)


def supports(config: KVConfig) -> bool:
    """Whether this config can run the fused GET program. Everything
    outside this set silently rides the composed XLA path — the fallback
    matrix documented in README "Fused device kernels"."""
    if config.index.kind not in FUSED_FAMILIES:
        return False
    if not config.paged:
        return False
    # pow2 page width: the kernel's xor tree-fold digest requires it
    # (composed uses a ufunc reduce, equal on pow2)
    pw = config.page_words
    return not pw & (pw - 1)


def resolve(config: KVConfig) -> bool:
    """Construction-time fused/composed decision: `PMDFC_FUSED` over
    `KVConfig.fused_get`; 'auto' fuses on TPU only, 'on' forces the
    kernel anywhere (interpret mode off-chip — the conformance drills'
    configuration), 'off' forces composed. Unsupported configs are never
    fused regardless of mode.

    Publishes the decision as the `serving.fused_get` gauge (0|1) so
    observers (teletop's kernel-path indicator, teledumps) can tell
    which GET program a server is actually running."""
    mode = fused_mode(config.fused_get)
    if mode == "off" or not supports(config):
        fused = False
    elif mode == "on":
        fused = True
    else:
        fused = jax.default_backend() == "tpu"
    from pmdfc_tpu.runtime import telemetry as tele

    tele.get().scope("serving", unique=False).gauge("fused_get").set(
        1 if fused else 0)
    return fused


def _interpret() -> bool:
    """Pallas interpret mode everywhere but a TPU backend (the AOT
    compile tests patch this to lower for a described chip)."""
    return jax.default_backend() != "tpu"


def tile_for(w: int) -> int:
    """Keys per kernel grid step. 128 keys × a 4 KB page is a 512 KB
    output block + one 64 KB bucket-row block — comfortably inside VMEM
    with double-buffering headroom; smaller padded batches take their
    whole width in one step (w is a pow2 off the pad ladder)."""
    return min(w, 128)


def _digest_rows(pages: jnp.ndarray) -> jnp.ndarray:
    """`pagepool.page_digest` with the lane xor-fold as an explicit
    halving tree (xor is associative+commutative, so this is bit-identical
    to the composed ufunc reduce; Mosaic lowers pow2 halvings cleanly)."""
    n = pages.shape[-1]
    lanes = jax.lax.broadcasted_iota(jnp.uint32, pages.shape, 1)
    mixed = (pages ^ (lanes * jnp.uint32(_LANE_SALT))) \
        * jnp.uint32(_FNV_PRIME)
    x = mixed ^ (mixed >> 15)
    while n > 1:
        n //= 2
        x = x[:, :n] ^ x[:, n:2 * n]
    h = x[:, 0] * jnp.uint32(_FINAL_MIX)
    return h ^ (h >> 13)


def _get_kernel(*refs, family, tiered, S, W, Gmax, msb, NR, T):
    """One grid step = `T` keys through probe, match, page gather and
    digest (module docstring stages 1-4). Ref layout is positional per
    `_pallas_get`."""
    i = 0
    keys_ref = refs[i]; i += 1
    table_ref = refs[i]; i += 1
    if family == "cceh":
        dirr_ref = refs[i]; i += 1
    pages_ref = refs[i]; i += 1
    out_ref, code_ref, rows_ref, slots_ref, vhi_ref, dig_ref = refs[i:i + 6]
    i += 6
    brow_ref = refs[i]; i += 1     # VMEM [T, 4S] bucket rows
    grp1_ref = refs[i]; i += 1     # VMEM [DEPTH, 8, 4S] bucket-row groups
    grp2_ref = refs[i]; i += 1     # VMEM [DEPTH, 8, PW] page-row groups
    a1v_ref = refs[i]; i += 1      # VMEM [A1, T] round-1 addresses
    a1s_ref = refs[i]; i += 1      # SMEM twin (DMA indices live in SMEM)
    rowv_ref = refs[i]; i += 1     # VMEM [1, T] resolved table row ids
    rows_s_ref = refs[i]; i += 1   # SMEM twin
    a2v_ref = refs[i]; i += 1      # VMEM [1, T] pool rows to gather
    a2s_ref = refs[i]; i += 1      # SMEM twin
    sem_cp = refs[i]; i += 1       # local VMEM<->SMEM copies
    sem1 = refs[i]; i += 1         # probe-round stream [DEPTH]
    sem2 = refs[i]; i += 1         # gather-round stream [DEPTH]
    d = min(_DEPTH, T)

    # -- stage 1: address fold (vector) -> SMEM ---------------------------
    keys = keys_ref[...]
    khi, klo = keys[:, 0], keys[:, 1]
    h = hash_u64(khi, klo)
    if family == "cceh":
        if msb:
            bucket = (h >> (32 - Gmax)).astype(jnp.int32)
        else:
            bucket = (h & jnp.uint32((1 << Gmax) - 1)).astype(jnp.int32)
        hwin = (hash_u64(khi, klo, seed=WINDOW_SEED)
                & jnp.uint32(W - 1)).astype(jnp.int32)
    else:
        bucket = (h & jnp.uint32(table_ref.shape[0] - 1)).astype(jnp.int32)
    a1v_ref[0, :] = bucket
    if family == "cceh":
        a1v_ref[1, :] = hwin
    _local_copy(a1v_ref, a1s_ref, sem_cp)

    # resolved table row per key: cceh walks the SMEM directory (scalar
    # loop — the probe address depends on a replicated-dir deref); linear
    # rows are the bucket hash itself
    if family == "cceh":
        def walk(i, _):
            rows_s_ref[0, i] = dirr_ref[a1s_ref[0, i]] * W + a1s_ref[1, i]
            return _

        jax.lax.fori_loop(0, T, walk, 0)
        _local_copy(rows_s_ref, rowv_ref, sem_cp)
        trow_s = rows_s_ref
    else:
        trow_s = a1s_ref

    # -- stage 2: probe DMA pipeline (bucket rows) ------------------------
    _pipeline(table_ref, grp1_ref, brow_ref, lambda i: trow_s[0, i],
              sem1, T, d)

    # -- stage 3: match (vector, exactly `get_batch`'s lane semantics) ----
    brows = brow_ref[...]
    eq = match_mask(brows, keys, S)
    found0 = eq.any(axis=1)
    vhi = lane_pick(brows, eq, 2 * S, S)
    vlo = lane_pick(brows, eq, 3 * S, S)
    lane_iota = jax.lax.broadcasted_iota(jnp.int32, (T, S), 1)
    lane = jnp.min(jnp.where(eq, lane_iota, jnp.int32(S)), axis=1)
    trow_vec = rowv_ref[0, :] if family == "cceh" else a1v_ref[0, :]
    gslot = jnp.where(found0, trow_vec * S + jnp.minimum(lane, S - 1),
                      jnp.int32(-1))
    rowv = vlo.astype(jnp.int32)
    if tiered:
        tag = vhi >> 30
        nopage = found0 & (tag == jnp.uint32(3))
        ext = found0 & (tag != jnp.uint32(0)) & ~nopage
    else:
        ext = found0 & (vhi == jnp.uint32(_EXTENT_TAG))
        nopage = jnp.zeros_like(found0)
    f1 = found0 & ~ext & ~nopage

    # -- stage 4: page gather DMA pipeline --------------------------------
    a2v_ref[0, :] = jnp.clip(jnp.where(f1, rowv, 0), 0, NR - 1)
    _local_copy(a2v_ref, a2s_ref, sem_cp)
    _pipeline(pages_ref, grp2_ref, out_ref, lambda i: a2s_ref[0, i],
              sem2, T, d)

    # -- stage 5: digest (vector) + pre-classification --------------------
    # index misses leave as COLD and page candidates as HIT; `get_core`'s
    # XLA epilogue splits off evicted / stale / parked / digest (their
    # sidecars are single words, which Mosaic cannot DMA per key)
    valid = ~is_invalid(keys)
    code = jnp.full((T,), CAUSE_HIT, jnp.int32)
    code = jnp.where(~valid, CAUSE_PAD, code)
    code = jnp.where(valid & ~found0, CAUSE_COLD, code)
    code = jnp.where(ext, CAUSE_EXT, code)
    code = jnp.where(nopage, CAUSE_PARKED, code)
    code_ref[0, :] = code
    rows_ref[0, :] = jnp.where(f1, rowv, jnp.int32(-1))
    slots_ref[0, :] = gslot
    vhi_ref[0, :] = vhi
    dig_ref[0, :] = _digest_rows(out_ref[...])


def _local_copy(src, dst, sem):
    """One local DMA between VMEM and SMEM (vector results become DMA
    addresses, which the scalar core reads from SMEM)."""
    cp = pltpu.make_async_copy(src, dst, sem.at[0])
    cp.start()
    cp.wait()


def _pipeline(src_ref, grp_ref, dst_ref, row_of, sem, t, d):
    """Row gather `dst[i] = src[row_of(i)]` for i < t as a DMA ring of
    depth `d`: warm `d` keys, steady wait(i-d)/start(i), drain the tail.

    HBM arrays are (8, 128)-tiled, and Mosaic only DMAs whole tiles: a
    lone row is a sub-tile slice it refuses. So each key fetches the
    aligned 8-row group holding its row into ring slot `i % d`, and the
    row is copied out of VMEM (a dynamic-sublane load) when it lands."""

    def copy(i):
        base = pl.multiple_of((row_of(i) // _SUB) * _SUB, _SUB)
        return pltpu.make_async_copy(
            src_ref.at[pl.ds(base, _SUB)], grp_ref.at[i % d], sem.at[i % d])

    def land(i):
        copy(i).wait()
        dst_ref[pl.ds(i, 1), :] = grp_ref[i % d, pl.ds(row_of(i) % _SUB, 1), :]

    def warm(i, _):
        copy(i).start()
        return _

    jax.lax.fori_loop(0, d, warm, 0)

    def steady(i, _):
        land(i - d)
        copy(i).start()
        return _

    jax.lax.fori_loop(d, t, steady, 0)

    def drain(i, _):
        land(i)
        return _

    jax.lax.fori_loop(t - d, t, drain, 0)


def _pallas_get(keys, table, dirr, pages, *, family, tiered, S, W, Gmax,
                msb, tile):
    """Build + launch the fused kernel over the padded batch. Returns
    (out[w, PW] raw gathered bytes, code[w] pre-classification, rows[w]
    page-candidate pool rows or -1, slots[w], vhi[w], dig[w] digests of
    the gathered bytes) — `get_core` finishes the verdict in XLA."""
    w = keys.shape[0]
    nr, pw = pages.shape
    t = min(tile, w)
    lanes = table.shape[1]
    grid = (w // t,)
    interpret = _interpret()

    from pmdfc_tpu.runtime import telemetry as tele

    tele.track_program(
        "kv.get_fused.kernel",
        (family, tiered, w, t, pw, lanes, interpret),
        detail=f"family={family},w={w},tile={t},vw={pw}",
    )

    kern = partial(_get_kernel, family=family, tiered=tiered, S=S, W=W,
                   Gmax=Gmax, msb=msb, NR=nr, T=t)
    in_specs = [pl.BlockSpec((t, 2), lambda g: (g, 0)),
                pl.BlockSpec(memory_space=pl.ANY)]
    args = [keys, table]
    if family == "cceh":
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(dirr)
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    args.append(pages)

    a1 = 2 if family == "cceh" else 1
    d = min(_DEPTH, t)
    row_spec = pl.BlockSpec((1, t), lambda g: (0, g))
    out, code, rows, slots, vhi, dig = pl.pallas_call(
        kern,
        grid=grid,
        out_shape=[
            jax.ShapeDtypeStruct((w, pw), jnp.uint32),
            jax.ShapeDtypeStruct((1, w), jnp.int32),
            jax.ShapeDtypeStruct((1, w), jnp.int32),
            jax.ShapeDtypeStruct((1, w), jnp.int32),
            jax.ShapeDtypeStruct((1, w), jnp.uint32),
            jax.ShapeDtypeStruct((1, w), jnp.uint32),
        ],
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((t, pw), lambda g: (g, 0))] + [row_spec] * 5,
        scratch_shapes=[
            pltpu.VMEM((t, lanes), jnp.uint32),
            pltpu.VMEM((d, _SUB, lanes), jnp.uint32),
            pltpu.VMEM((d, _SUB, pw), jnp.uint32),
            pltpu.VMEM((a1, t), jnp.int32),
            pltpu.SMEM((a1, t), jnp.int32),
            pltpu.VMEM((1, t), jnp.int32),
            pltpu.SMEM((1, t), jnp.int32),
            pltpu.VMEM((1, t), jnp.int32),
            pltpu.SMEM((1, t), jnp.int32),
            pltpu.SemaphoreType.DMA((1,)),
            pltpu.SemaphoreType.DMA((d,)),
            pltpu.SemaphoreType.DMA((d,)),
        ],
        interpret=interpret,
    )(*args)
    return out, code[0], rows[0], slots[0], vhi[0], dig[0]


def get_core(state, config: KVConfig, keys: jnp.ndarray,
             lean: bool = False, recovering: bool = False):
    """Fused twin of `kv._get_core`: same signature, same returns
    (state', out, found), bit-identical outputs/stats/cause lanes. Falls
    back to the composed program for anything `supports()` excludes, and
    for geometry the group DMAs cannot tile (row counts off the 8-row
    group) — the zero-behavior-change contract behind PMDFC_FUSED=auto."""
    from pmdfc_tpu import kv as kv_mod

    tiered = isinstance(state.pool, tier_mod.TierState)
    flat = isinstance(state.pool, pagepool.PoolState)
    if (not supports(config) or not (tiered or flat)
            or state.index.table.shape[0] % _SUB
            or state.pool.pages.shape[0] % _SUB):
        return kv_mod._get_core(state, config, keys, lean=lean,
                                recovering=recovering)

    from pmdfc_tpu.models.base import get_index_ops

    assert kv_mod.EXTENT_TAG == _EXTENT_TAG
    ops = get_index_ops(config.index.kind)
    table = state.index.table
    S = table.shape[1] // 4
    if config.index.kind == IndexKind.CCEH:
        family, dirr = "cceh", state.index.dirr
        smax = state.index.ld.shape[0]
        W = table.shape[0] // smax
        Gmax = smax.bit_length() - 1
        msb = state.index.msb
    else:
        family, dirr = "linear", None
        W, Gmax, msb = 1, 0, True
    pool = state.pool

    out, code, rows, slots, vhi, dig = _pallas_get(
        keys, table, dirr, pool.pages, family=family, tiered=tiered, S=S,
        W=W, Gmax=Gmax, msb=msb, tile=tile_for(keys.shape[0]),
    )
    # -- XLA epilogue: the sidecar verdicts, as `_get_core` computes them
    f1 = code == CAUSE_HIT
    if tiered:
        # generation gate, then liveness, then digest (`tier` helpers)
        cur = tier_mod.entry_current(
            pool, jnp.stack([vhi, rows.astype(jnp.uint32)], -1))
        stale = f1 & ~cur
        f2 = f1 & cur
        rows = jnp.where(f2, rows, jnp.int32(-1))
        live = tier_mod.row_live(pool, rows)
        sums_ok = dig == tier_mod.stored_sums(pool, rows)
        dead = f2 & ~live
        corrupt = f2 & live & ~sums_ok
    else:
        stale = dead = jnp.zeros_like(f1)
        corrupt = f1 & (dig != pool.sums[jnp.maximum(rows, 0)])
    ev = (code == CAUSE_COLD) & kv_mod._sketch_query(state, config, keys)
    cause = jnp.where(ev, CAUSE_EVICTED, code)
    cause = jnp.where(stale, CAUSE_STALE, cause)
    cause = jnp.where(dead, CAUSE_PARKED, cause)
    cause = jnp.where(corrupt, CAUSE_DIGEST, cause)
    found = cause == CAUSE_HIT
    out = jnp.where(found[:, None], out, jnp.uint32(0))
    valid = ~is_invalid(keys)

    if tiered and not lean:
        # hotness/migration epilogue: scatter-heavy state update, rides
        # composed XLA inside this same jitted program (same cadence
        # contract as the composed counting path)
        new_index, new_pool = tier_mod.on_get(
            ops, state.index, state.pool, kv_mod._tcfg(config), keys,
            slots, rows, out, found,
        )
        state = dataclasses.replace(state, index=new_index, pool=new_pool)

    def cnt(m):
        return m.sum(dtype=jnp.int32)

    bumps = jnp.zeros((kv_mod.NSTATS,), jnp.int32)
    bumps = bumps.at[kv_mod.GETS].add(cnt(valid))
    bumps = bumps.at[kv_mod.HITS].add(cnt(found))
    bumps = bumps.at[kv_mod.MISSES].add(cnt(valid & ~found))
    bumps = bumps.at[kv_mod.CORRUPT_PAGES].add(cnt(corrupt))
    bumps = bumps.at[kv_mod.MISS_EVICTED].add(cnt(ev))
    bumps = bumps.at[kv_mod.MISS_COLD].add(
        cnt((cause == CAUSE_COLD) | (cause == CAUSE_EXT)))
    bumps = bumps.at[kv_mod.MISS_PARKED].add(cnt(cause == CAUSE_PARKED))
    bumps = bumps.at[kv_mod.MISS_STALE].add(cnt(stale))
    bumps = bumps.at[kv_mod.MISS_DIGEST].add(cnt(corrupt))
    if recovering:
        bumps = kv_mod._reattribute_recovering(bumps)
    state = dataclasses.replace(state, stats=state.stats + bumps)
    return state, out, found
