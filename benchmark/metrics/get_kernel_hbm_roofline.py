"""Device programs + kernel: the fused GET kernel's own share of the HBM
roofline. The needed bytes of the traced slice's GET keys
(`roofline.get_bytes_per_key`: a bucket row read, a page read and a page
written) over the summed device time of the kernel's ops alone, the `XLA
Ops` events whose instruction is named after the kernel's `pallas_call`
(names in `get_kernel_hbm_roofline.json`). The bytes are the work's: the
kernel's 8-row group DMAs read 8x the rows, and show as a lower share.
None where no op of the kernel ran (the composed GET)."""

import json
import os

from benchmark import roofline, trace


def _mine(name: str, prefixes) -> bool:
    """Whether an op event is the kernel's own instruction: its name, or
    the left side of its HLO text (`%pmdfc_fused_get.1 = ...`), starts
    with a kernel name; an op that only reads the kernel's output does
    not count."""
    lhs = name.split(" = ", 1)[0].lstrip("%")
    return any(lhs.startswith(p) for p in prefixes)


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.devices:
        return None
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "get_kernel_hbm_roofline.json")) as f:
        names = json.load(f)["ops"]
    t = sum(trace.covered_s([e for e in d.ops if _mine(e[0], names)],
                            tr.window) for d in tr.devices) / len(tr.devices)
    keys = ctx["trace_prx1"]["get_keys"] - ctx["trace_prx0"]["get_keys"]
    per_key = roofline.get_bytes_per_key(ctx["cfg"]["index_row_bytes"],
                                         4 * ctx["cfg"]["kv"]["page_words"])
    return roofline.roofline_share(keys, per_key, len(tr.devices),
                                   roofline.peaks(ctx["device_kind"])
                                   ["hbm_bytes_per_s"], t)
