"""Test env: an 8-device virtual CPU mesh; tests never touch a chip.

Sharding tests run over `--xla_force_host_platform_device_count=8` on CPU
(same trick the driver's `dryrun_multichip` uses). The chip is reached only
through `python chip_smoke.py`; `tests/test_chip_compile.py` compiles for
a described v5e without one.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compile cache: one source of truth in bench/common —
# cuts the full suite 990s -> ~400s warm, shared with the bench
# harnesses. Includes atomic entry writes
# and single-device-only serialization (see the helper's docstring).
# Disable with PMDFC_COMPILE_CACHE=0. A day of wandering full-suite
# segfaults was initially pinned on this cache, but bisection exonerated
# it — the real cause was vm.max_map_count exhaustion (see below).
from pmdfc_tpu.bench.common import enable_compile_cache  # noqa: E402

enable_compile_cache()

import pytest  # noqa: E402


def _ensure_map_headroom() -> bool:
    """Raise vm.max_map_count if this process may (root containers).

    jax's in-process executable cache grows monotonically; a full-suite run
    accumulates >65k memory mappings (JIT code pages + buffers), crosses
    the kernel's 65530 default, and the next mmap failure SEGFAULTS inside
    XLA's compiler — observed as wandering crashes at ~90% of every full
    run once the suite grew past the limit. Peak measured: 64 890 maps.

    Host-wide kernel sysctl: opt out with PMDFC_RAISE_MAP_COUNT=0 (the
    per-module jax.clear_caches() fallback below then bounds the map count
    instead, at ~1-2 min of recompiles per full run); any mutation is
    logged to stderr (round-3 advisor finding: silent side effect).
    """
    import sys

    path = "/proc/sys/vm/max_map_count"
    try:
        before = int(open(path).read())
        if (before < 262144
                and os.environ.get("PMDFC_RAISE_MAP_COUNT", "1") != "0"):
            open(path, "w").write("262144")
            print(f"[conftest] raised vm.max_map_count {before} -> 262144 "
                  "(host-wide; PMDFC_RAISE_MAP_COUNT=0 to disable)",
                  file=sys.stderr)
        # opt-out guards only the WRITE: a host that already has headroom
        # (pre-raised by its operator) must not pay the clear_caches fallback
        return int(open(path).read()) >= 200000
    except OSError:
        return False


_MAP_HEADROOM = _ensure_map_headroom()


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    """Fallback when the kernel ceiling could not be raised: drop compiled
    executables after each module, keeping the map count sawtoothing near
    32k (far under 65530). Costs ~1-2 min of recompiles-from-disk per full
    run, so it only runs when actually needed."""
    yield
    if not _MAP_HEADROOM:
        jax.clear_caches()
