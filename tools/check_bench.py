#!/usr/bin/env python
"""check_bench — bench-lane regression gate over BENCH_HISTORY.jsonl.

Every bench main appends evidence rows through the one shared logger
(`bench/common.append_history`). This tool groups those rows into LANES
— all identity fields equal: metric, transport, index, verb/shape
knobs, device, telemetry on/off, ... everything except the measured
value and the timestamp — and compares each lane's FRESHEST row against
the previous row of the same lane with a tolerance band. A throughput
lane (Mpages/s, Mops/s, ...) regresses when the fresh value drops below
`prev * (1 - tolerance)`; a latency lane (us/ms/s units) regresses when
it rises above `prev * (1 + tolerance)`. Exit 1 on any regression, so
the agenda can gate on it right after the smoke benches (step
`bench_gate`).

    python tools/check_bench.py BENCH_HISTORY.jsonl [--tolerance 0.15]
        [--metric telemetry_overhead] [--max-age-h 48]

`--max-age-h` only checks lanes whose freshest row is recent (default:
all) — an old lane that simply wasn't re-run is not a regression.

Importable: `lane_key(row)`, `check_history(rows, tolerance) ->
regressions` — tests/test_tracing.py pins the comparison semantics.
"""

from __future__ import annotations

import argparse
import datetime
import json
import sys

# explicitly measured outputs, never lane identity
VALUE_KEYS = frozenset({"ts", "value", "wall_s", "overhead_ratio"})
# int-typed fields that are nevertheless RESULTS (the int/float type
# split below is the main classifier; these are its exceptions).
# `hits` is the fused_get sweep's workload outcome — deterministic per
# seed today, but an eviction-policy change must not silently fork the
# lane. `kernel` (pallas_fused | xla_composed) and `tile` ARE identity:
# the paired fused-vs-composed rows may never collapse into one lane,
# or the gate would read the slower kernel as a regression of the
# faster one.
MEASURED_INT_KEYS = frozenset({"failed_search", "gather_bytes_per_s",
                               "spans_recorded", "hits"})
# float-typed fields that are KNOBS (zipf exponents and the like)
FLOAT_KNOB_KEYS = frozenset({"zipf", "theta", "alpha", "hedge_ms"})
# units where smaller is better; anything else is treated as throughput
# (`fetch_us` is the profiler's blocked-fetch lane — host microseconds
# waiting on the chip, so lower is better like any latency; history rows
# from before its rename carry it as `device_us`)
LATENCY_UNITS = frozenset({"ns", "us", "ms", "s", "fetch_us", "device_us"})


def lane_key(row: dict) -> str:
    """Lane identity = the row's qualitative stamps and shape knobs.

    History rows interleave knobs with SECONDARY measured outputs
    (best_wall_s, link_h2d_mbs, p99_batch_ms, ...) that differ every
    run — treating those as identity would make every row a singleton
    lane and the gate vacuous. The type split matches how the benches
    actually write rows: strings/bools/ints are stamps and knobs
    (minus the known measured-int exceptions), floats are measurements
    (minus the known float knobs), None/lists are never identity."""
    ident = {}
    for k, v in row.items():
        if k in VALUE_KEYS or k in MEASURED_INT_KEYS:
            continue
        if isinstance(v, (str, bool)) or isinstance(v, int):
            ident[k] = v
        elif isinstance(v, float) and k in FLOAT_KNOB_KEYS:
            ident[k] = v
    return json.dumps(ident, sort_keys=True)


def _parse_ts(row: dict):
    try:
        return datetime.datetime.fromisoformat(row["ts"])
    except (KeyError, ValueError):
        return None


def check_history(rows: list[dict], tolerance: float = 0.15,
                  metric: str | None = None,
                  max_age_h: float | None = None) -> list[dict]:
    """Regressions across all lanes with >= 2 rows (file order = time
    order within a lane; append_history only ever appends)."""
    lanes: dict[str, list[dict]] = {}
    for row in rows:
        if "value" not in row or "metric" not in row:
            continue
        if metric is not None and row["metric"] != metric:
            continue
        lanes.setdefault(lane_key(row), []).append(row)
    now = datetime.datetime.now(datetime.timezone.utc)
    out = []
    for key, rs in lanes.items():
        if len(rs) < 2:
            continue
        prev, cur = rs[-2], rs[-1]
        if max_age_h is not None:
            ts = _parse_ts(cur)
            if ts is None or (now - ts).total_seconds() > max_age_h * 3600:
                continue
        try:
            pv, cv = float(prev["value"]), float(cur["value"])
        except (TypeError, ValueError):
            continue
        if pv <= 0:
            continue  # no meaningful band around a zero baseline
        lower_better = str(cur.get("unit", "")).strip() in LATENCY_UNITS
        ratio = cv / pv
        bad = (ratio > 1 + tolerance) if lower_better \
            else (ratio < 1 - tolerance)
        if bad:
            out.append({
                "metric": cur.get("metric"),
                "unit": cur.get("unit"),
                "prev": pv, "cur": cv, "ratio": round(ratio, 4),
                "direction": "lower-better" if lower_better
                             else "higher-better",
                "tolerance": tolerance,
                "lane": key,
                "prev_ts": prev.get("ts"), "cur_ts": cur.get("ts"),
            })
    return out


def load_history(path: str) -> list[dict]:
    rows = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rows.append(json.loads(line))
            except ValueError:
                print(f"[check_bench] skipping unparseable line: "
                      f"{line[:80]}", file=sys.stderr)
    return rows


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("history", help="BENCH_HISTORY.jsonl path")
    p.add_argument("--tolerance", type=float, default=0.15,
                   help="allowed fractional drift (default 0.15)")
    p.add_argument("--metric", default=None,
                   help="restrict to one metric name")
    p.add_argument("--max-age-h", type=float, default=None,
                   help="only gate lanes whose fresh row is younger "
                        "than this many hours")
    args = p.parse_args(argv)

    rows = load_history(args.history)
    lanes = {lane_key(r) for r in rows if "value" in r}
    regs = check_history(rows, tolerance=args.tolerance,
                         metric=args.metric, max_age_h=args.max_age_h)
    if regs:
        for r in regs:
            print(f"[check_bench] REGRESSION {r['metric']} "
                  f"({r['direction']}, unit={r['unit']}): "
                  f"{r['prev']} -> {r['cur']} (x{r['ratio']}, "
                  f"tolerance {r['tolerance']})\n"
                  f"  lane: {r['lane']}", file=sys.stderr)
        print(f"[check_bench] FAIL: {len(regs)} regressed lane(s) of "
              f"{len(lanes)}", file=sys.stderr)
        return 1
    print(f"[check_bench] OK: {len(lanes)} lanes, none regressed "
          f"beyond {args.tolerance:.0%}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
