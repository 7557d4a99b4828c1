"""Shared backend construction for the bench/harness CLIs.

One place builds the client-side Backend from CLI-ish parameters — the
bench mains (`paging_sim`, `filebench`, `multinode`, `train_pressure`)
must not each hand-roll the KVConfig/backend matrix (they diverge
silently otherwise).
"""

from __future__ import annotations

import os


REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", ".jax_cache"))


def enable_compile_cache() -> None:
    """Persistent XLA compile cache — the ONE source of truth for cache
    setup (tests/conftest.py calls this too). Disable with
    PMDFC_COMPILE_CACHE=0.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and
    nothing here overrides it; otherwise the cache lives at the fixed
    `<repo>/.jax_cache` (the path is part of the cache key, so a moving
    directory would never hit).

    Two pieces of hardening ride along, both patches of jax 0.9.0's
    private `jax._src` cache internals:
    - Atomic entry writes: `LRUCache.put` uses a bare `write_bytes`; a
      process killed mid-write leaves a truncated entry that SEGFAULTS
      the XLA deserializer on a later run (observed twice). Temp-file +
      rename means readers only ever see whole entries.
    - Single-device-only serialization: jaxlib 0.9's executable
      (de)serializer is not trusted for multi-device CPU executables;
      skipping them costs little (shard_map programs are few).
    """
    if os.environ.get("PMDFC_COMPILE_CACHE", "1") == "0":
        return
    import jax
    import jax._src.compilation_cache as _cc
    import jax._src.lru_cache as _lru

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    if getattr(_lru.LRUCache.put, "_pmdfc_atomic", False):
        return  # already hardened (idempotent under repeat calls)

    _orig_put = _lru.LRUCache.put

    def _atomic_put(self, key, val):
        if self.eviction_enabled:  # locked path does its own bookkeeping
            return _orig_put(self, key, val)
        if not key:
            raise ValueError("key cannot be empty")
        cache_path = self.path / f"{key}{_lru._CACHE_SUFFIX}"
        if cache_path.exists():
            return
        tmp = cache_path.with_name(cache_path.name + f".tmp{os.getpid()}")
        try:
            tmp.write_bytes(val)
            os.replace(tmp, cache_path)
        except OSError:
            try:
                tmp.unlink()
            except OSError:
                pass

    _atomic_put._pmdfc_atomic = True
    _lru.LRUCache.put = _atomic_put

    _orig_put_exec = _cc.put_executable_and_time

    def _single_device_put_exec(cache_key, module_name, executable, backend,
                                compile_time):
        try:
            ndev = len(executable.local_devices())
        except Exception:  # noqa: BLE001 — be conservative, skip caching
            return
        if ndev > 1:
            return
        return _orig_put_exec(cache_key, module_name, executable, backend,
                              compile_time)

    _cc.put_executable_and_time = _single_device_put_exec


def stamp_live_device(out: dict, backend: str) -> None:
    """Stamp the evidence row with where the workload ACTUALLY ran.

    The one stamping implementation for every bench main (charter rule:
    no per-harness hand-rolls or the rows diverge). The pure-numpy
    `local` backend never touches a device — stamping jax's platform
    would record a host-dict workload as on-chip evidence on a TPU
    host, so it stamps itself non-tpu (the history guard refuses it)."""
    if backend == "local":
        out["device"] = "local-host"
        out["device_kind"] = "host-dict"
    else:
        import jax

        dev = jax.devices()[0]
        out["device"] = dev.platform
        out["device_kind"] = dev.device_kind


def append_history(path: str | None, record: dict) -> None:
    """Append one UTC-timestamped JSON line to the evidence log at `path`.

    The ONE history-append implementation for every bench main (test_kv,
    swap_sim, paging_sim) — per this module's charter, shared bookkeeping
    must not be hand-rolled per harness or the row schemas diverge
    silently. No-op when `path` is falsy; an OSError is reported to
    stderr, never raised (evidence logging must not cost the run).

    The log is ON-CHIP evidence: a record stamped with a non-tpu device
    is refused here, centrally, so no harness can pollute the history a
    CPU fallback (every caller stamps `device` from the live backend).
    Exception: rows carrying `host_evidence: True` (transport-tier
    benches like `net_sweep`, whose subject is the wire + scheduler, not
    the chip) are appended with their honest device stamp — the stamp
    requirement itself still holds."""
    if not path:
        return
    import datetime
    import json
    import sys

    dev = record.get("device")
    if dev is None and record.get("host_evidence"):
        # host rows are exempt from the on-chip gate, never from the
        # honest-stamp requirement
        print("[bench] refusing history append: host_evidence record "
              "carries no device stamp", file=sys.stderr)
        return
    if dev != "tpu" and not record.get("host_evidence"):
        # An honestly-stamped off-chip record (cpu fallback, local run) is
        # skipped silently — that is normal operation, not an error. Only
        # a MISSING stamp is loud: the forgot-to-stamp case is exactly
        # what a central guard exists to catch (ADVICE r4: the
        # unconditional message turned every supervised CPU fallback into
        # misleading refusal noise).
        if dev is None:
            print("[bench] refusing history append: record carries no "
                  "device stamp", file=sys.stderr)
        return

    try:
        with open(path, "a") as f:
            f.write(json.dumps({
                "ts": datetime.datetime.now(
                    datetime.timezone.utc).isoformat(),
                **record,
            }) + "\n")
    except OSError as e:
        print(f"[bench] history append to {path} failed: {e}",
              file=sys.stderr)


def pin_cpu() -> None:
    """Pin jax to the CPU before backend init (a harness's `--cpu` run
    on a host that also has a chip)."""
    import jax

    jax.config.update("jax_platforms", "cpu")


def build_backend(kind: str, page_words: int, capacity: int,
                  bloom_bits: int = 1 << 22, device: str = "cpu",
                  tier=None):
    """Backend of `kind` in {"local", "direct", "engine"}.

    Returns `(backend, closer)`; call `closer()` at teardown (stops the
    KVServer for the engine path; no-op otherwise). `tier` (a
    `TierConfig`, optionally carrying an `AdmitConfig`) selects the
    tiered page store for the direct/engine paths — the scan-mix
    harness prices the admission gate through it; the pure-numpy
    `local` backend has no tiers and ignores it.
    """
    if kind == "local":
        from pmdfc_tpu.client import LocalBackend

        return LocalBackend(page_words, capacity), lambda: None

    if device == "cpu":
        pin_cpu()
    from pmdfc_tpu.config import BloomConfig, IndexConfig, KVConfig

    cfg = KVConfig(
        index=IndexConfig(capacity=capacity),
        bloom=BloomConfig(num_bits=bloom_bits),
        paged=True, page_words=page_words, tier=tier,
    )
    if kind == "direct":
        from pmdfc_tpu.client import DirectBackend
        from pmdfc_tpu.kv import KV

        return DirectBackend(KV(cfg)), lambda: None
    if kind == "engine":
        from pmdfc_tpu.client import EngineBackend
        from pmdfc_tpu.runtime import Engine, KVServer

        # Cache first, then warm the flush ladder BEFORE admitting
        # clients: with 1024-word pages each width's first XLA compile
        # costs seconds on CPU, and an unwarmed driver compiling mid-flush outlasts a synchronous client's patience (observed:
        # swap_sim's first 128-page store timing out at 10 s while the
        # driver was still inside backend_compile_and_load). The compile
        # cache makes this a once-per-host cost; the client timeout still
        # allows for one uncached straggler shape.
        enable_compile_cache()
        eng = Engine(arena_pages=1 << 10, page_bytes=page_words * 4)
        server = KVServer(cfg, engine=eng).start()
        server.warmup(max_width=1 << 10)
        backend = EngineBackend(server, timeout_us=120_000_000)

        def closer():
            backend.close()
            server.stop()

        return backend, closer
    raise ValueError(f"unknown backend kind {kind!r}")
