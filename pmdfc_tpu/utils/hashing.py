"""Vectorized 64-bit-key hashing on uint32 lanes.

The reference dispatches between std/murmur2/jenkins/xxhash behind `h()`
(`server/util/hash.h:240-252`) operating on 8-byte keys. TPUs have no native
64-bit integers worth using, so keys are (hi, lo) uint32 pairs and the hash is
a murmur3-32 over the two words — fully vectorized, wraparound uint32
arithmetic that XLA lowers to plain VPU ops.

Different consumers need independent hash families (bloom filter k-hashes,
cuckoo's two hashes, shard routing); `hash_u64(hi, lo, seed)` gives one family
member per seed.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

# numpy scalars, NOT jnp: a module-level jnp constant initializes the JAX
# backend at import time, and importing the package must never touch a
# device (a child of a process that holds the chip would fight it for the
# chip; child processes of the net/multinode harnesses import this
# jax-free).
_C1 = np.uint32(0xCC9E2D51)
_C2 = np.uint32(0x1B873593)


def _rotl32(x: jnp.ndarray, r: int) -> jnp.ndarray:
    return (x << r) | (x >> (32 - r))


def _fmix32(h: jnp.ndarray) -> jnp.ndarray:
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def hash_u64(hi: jnp.ndarray, lo: jnp.ndarray, seed: int = 0) -> jnp.ndarray:
    """murmur3-32 of the 8-byte key (hi<<32|lo); returns uint32 of same shape."""
    h1 = jnp.uint32(seed)
    for word in (lo.astype(jnp.uint32), hi.astype(jnp.uint32)):
        k = word * _C1
        k = _rotl32(k, 15)
        k = k * _C2
        h1 = h1 ^ k
        h1 = _rotl32(h1, 13)
        h1 = h1 * jnp.uint32(5) + jnp.uint32(0xE6546B64)
    h1 = h1 ^ jnp.uint32(8)  # total length in bytes
    return _fmix32(h1)


# ---------------------------------------------------------------------------
# The reference's four-family dispatcher `h()` (`server/util/hash.h:240-252`:
# std, murmur2, jenkins, xxhash over the 8-byte key). Same surface here, each
# family vectorized on (hi, lo) uint32 lanes with wraparound arithmetic.
# murmur3 (above) is the framework default; the others exist for parity and
# for consumers that want a different family per structure.
# ---------------------------------------------------------------------------

def hash_std(hi: jnp.ndarray, lo: jnp.ndarray, seed: int = 0) -> jnp.ndarray:
    """FNV-1a over the 8 key bytes (the `std::hash` stand-in)."""
    h = jnp.uint32(0x811C9DC5) ^ jnp.uint32(seed)
    prime = jnp.uint32(0x01000193)
    for word in (lo.astype(jnp.uint32), hi.astype(jnp.uint32)):
        for shift in (0, 8, 16, 24):
            h = (h ^ ((word >> shift) & jnp.uint32(0xFF))) * prime
    return h


def hash_murmur2(hi: jnp.ndarray, lo: jnp.ndarray, seed: int = 0) -> jnp.ndarray:
    """MurmurHash2 (32-bit) over the two key words — the family the
    reference's counting bloom filter salts (`counting_bloom_filter.h:249`)."""
    m = jnp.uint32(0x5BD1E995)
    h = jnp.uint32(seed) ^ jnp.uint32(8)
    for word in (lo.astype(jnp.uint32), hi.astype(jnp.uint32)):
        k = word * m
        k = k ^ (k >> 24)
        k = k * m
        h = (h * m) ^ k
    h = h ^ (h >> 13)
    h = h * m
    h = h ^ (h >> 15)
    return h


def hash_jenkins(hi: jnp.ndarray, lo: jnp.ndarray, seed: int = 0) -> jnp.ndarray:
    """Jenkins one-at-a-time over the 8 key bytes."""
    h = jnp.uint32(seed)
    for word in (lo.astype(jnp.uint32), hi.astype(jnp.uint32)):
        for shift in (0, 8, 16, 24):
            h = h + ((word >> shift) & jnp.uint32(0xFF))
            h = h + (h << 10)
            h = h ^ (h >> 6)
    h = h + (h << 3)
    h = h ^ (h >> 11)
    h = h + (h << 15)
    return h


def hash_xxh32(hi: jnp.ndarray, lo: jnp.ndarray, seed: int = 0) -> jnp.ndarray:
    """xxHash32 of the 8-byte key (small-input path: no stripe loop)."""
    p2 = jnp.uint32(0x85EBCA77)
    p3 = jnp.uint32(0xC2B2AE3D)
    p4 = jnp.uint32(0x27D4EB2F)
    p5 = jnp.uint32(0x165667B1)
    h = jnp.uint32(seed) + p5 + jnp.uint32(8)
    for word in (lo.astype(jnp.uint32), hi.astype(jnp.uint32)):
        h = h + word * p3
        h = _rotl32(h, 17) * p4
    h = h ^ (h >> 15)
    h = h * p2
    h = h ^ (h >> 13)
    h = h * p3
    h = h ^ (h >> 16)
    return h


FAMILIES = {
    "murmur3": hash_u64,
    "std": hash_std,
    "murmur2": hash_murmur2,
    "jenkins": hash_jenkins,
    "xxhash": hash_xxh32,
}


def h(hi: jnp.ndarray, lo: jnp.ndarray, seed: int = 0,
      family: str = "murmur3") -> jnp.ndarray:
    """The reference's `h()` dispatcher (`server/util/hash.h:240-252`)."""
    try:
        return FAMILIES[family](hi, lo, seed)
    except KeyError:
        raise ValueError(
            f"unknown hash family {family!r}; have {sorted(FAMILIES)}"
        ) from None


SHARD_SEED = 0x5EED5EED


def shard_of(keys: jnp.ndarray, n_shards: int) -> jnp.ndarray:
    """Key → owning shard, the `GetNodeID(key)` analog (`server/NuMA_KV.cpp:141`).

    Takes the canonical [..., 2] uint32 key layout; one murmur3 family member
    reserved for routing so shard choice is independent of every index's
    bucket choice.
    """
    h = hash_u64(keys[..., 0], keys[..., 1], seed=SHARD_SEED)
    return (h % jnp.uint32(n_shards)).astype(jnp.uint32)


def hash_u64_multi(
    hi: jnp.ndarray, lo: jnp.ndarray, num_hashes: int, seed_base: int = 0
) -> jnp.ndarray:
    """Stack of `num_hashes` independent hashes, shape (num_hashes, *key_shape).

    Mirrors the reference bloom filter's murmur2+salt family
    (`server/util/counting_bloom_filter.h:249-254`).
    """
    return jnp.stack(
        [
            hash_u64(hi, lo, seed=(seed_base + 0x9E3779B9 * (i + 1)) & 0xFFFFFFFF)
            for i in range(num_hashes)
        ]
    )
