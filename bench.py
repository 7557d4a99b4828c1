#!/usr/bin/env python
"""test_KV-equivalent benchmark entry point.

Runs the harness `pmdfc_tpu.bench.test_kv` (see its docstring for metric
definitions and the recorded baseline) in this process, on the chip. There
is no fallback: without a TPU it exits non-zero and prints no number.
Arguments pass through to the harness.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", ...}.
"""

from __future__ import annotations

import sys


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" or "--cpu" in sys.argv:
        print(f"bench.py: no TPU (JAX reports {dev.platform!r}); "
              "no number without the chip", file=sys.stderr)
        return 2
    from pmdfc_tpu.bench import test_kv

    test_kv.main()
    return 0


if __name__ == "__main__":
    sys.exit(main())
