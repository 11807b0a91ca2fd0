#!/usr/bin/env python
"""Per-op device-time + roofline summary from a profiler dump.

Merges three sources into one table (ISSUE 1 — restores the roofline
accounting XLA cost analysis loses for Pallas custom calls):

1. a chrome-trace JSON dump (``mx.profiler.dump()`` output, or a
   trace-viewer export; ``.json`` or ``.json.gz``) — per-op wall time from
   its "X" duration events, aggregated by name;
2. the custom-call cost registry — either embedded in the dump itself (the
   profiler inserts a ``custom_call_costs`` metadata event when the Pallas
   module is loaded), read from a telemetry JSONL event log or a plain
   ``{name: {flops, bytes_accessed}}`` JSON via ``--costs``, or pulled live
   from ``mxnet_tpu.ops.pallas_kernels`` with ``--live-registry``;
3. optionally whole-module XLA flops/bytes context — preferably from a
   compile-plane **cost ledger** (``--ledger``, the ``MXNET_COST_LEDGER``
   JSONL the library writes per compiled executable under
   ``MXNET_COSTPLANE=1``; ISSUE 13 — totals are summed over the last row
   per executable key, no hand-saving required), or from a hand-saved
   cost-analysis JSON (``--xla-cost``, the dict from
   ``jitted.lower(...).compile().cost_analysis()`` saved with json.dump).

Ops are matched to registered costs by case-insensitive substring (both
directions, plus each registry entry's aliases).  Registered custom calls
with no matching trace event still get a row (time "-") so declared costs
are always visible — a registered kernel can never be invisible again.

Usage::

    K="TPU v5 lite"    # jax.devices()[0].device_kind of the traced chip
    python tools/trace_summary.py profile.json --device-kind "$K"
    python tools/trace_summary.py profile.json --device-kind "$K" \
        --ledger cost_ledger.jsonl
    python tools/trace_summary.py profile.json --costs telemetry.jsonl \
        --peak-flops 197e12 --peak-bw 819e9 --top 20
    python tools/trace_summary.py profile.json --device-kind "$K" --json
    python tools/trace_summary.py rank0.json rank1.json --device-kind "$K" \
        --per-rank

**Per-rank inputs (ISSUE 12).**  A pod run produces one trace/flight dump
per process; pass them all — each file's rank is detected like
``tools/trace_merge.py`` does (``clock_sync`` args, per-event
``args.rank``, or a ``rank<N>`` filename token) and the op table merges
every rank's events into one accounting.  ``--per-rank`` keeps the ranks
apart instead (rows prefixed ``r<k>/``), which is how a straggler shows
up as one rank's ops running long.

Roofline: intensity = flops/bytes (declared), attainable = min(peak_flops,
intensity * peak_bw); %roof compares achieved FLOP/s (or B/s for zero-flop
ops) against it.  A trace does not say which chip produced it, so the peaks
have no default: name the chip (``--device-kind``, looked up in ``PEAKS``;
an unknown kind is an error) or give both ``--peak-flops`` and ``--peak-bw``.
"""
from __future__ import annotations

import argparse
import gzip
import json
import sys


# device_kind (as jax reports it) -> (bf16 FLOP/s, HBM B/s) of ONE chip.
# Source: Google Cloud documentation, "TPU v5e".
PEAKS = {"TPU v5 lite": (197e12, 819e9)}


def load_trace(path):
    """Chrome-trace JSON (optionally gzipped) → list of event dicts."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt", encoding="utf-8") as f:
        data = json.load(f)
    if isinstance(data, dict):
        return data.get("traceEvents", [])
    return data  # bare event-array form is also legal chrome-trace


def trace_rank(path, events):
    """The rank a per-rank trace belongs to, or None — THE
    ``trace_merge.file_rank`` detection (one implementation, one pod
    workflow: clock_sync args, unanimous event args.rank, filename
    token)."""
    import os

    try:
        import trace_merge
    except ImportError:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import trace_merge
    return trace_merge.file_rank(path, events)


def aggregate_ops(events, ops=None, prefix=""):
    """"X" duration events → {name: {"calls", "total_us"}} — pass ``ops``
    to accumulate several (per-rank) files into one table; ``prefix``
    keys rows per rank for --per-rank mode."""
    ops = {} if ops is None else ops
    for ev in events:
        if ev.get("ph") != "X" or "dur" not in ev:
            continue
        ent = ops.setdefault(prefix + ev.get("name", "?"),
                             {"calls": 0, "total_us": 0.0})
        ent["calls"] += 1
        ent["total_us"] += float(ev["dur"])
    return ops


def _norm_cost(ent):
    return {"flops": int(ent.get("flops", 0)),
            "bytes_accessed": int(ent.get("bytes_accessed", ent.get("bytes", 0))),
            "shape": ent.get("shape")}


def costs_from_trace(events):
    """The profiler-embedded ``custom_call_costs`` metadata event."""
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "custom_call_costs":
            return {k: _norm_cost(v) for k, v in (ev.get("args") or {}).items()}
    return {}


def costs_from_file(path):
    """--costs: telemetry JSONL (custom_call_cost events) or a plain
    {name: {flops, bytes_accessed}} JSON object."""
    with open(path, encoding="utf-8") as f:
        text = f.read().strip()
    if not text:
        return {}
    try:
        obj = json.loads(text)
        # a plain mapping {name: {flops, ...}} — but a single telemetry
        # event line is ALSO one valid JSON object, so require cost-shaped
        # values before treating the whole file as a mapping
        if (isinstance(obj, dict) and "traceEvents" not in obj
                and "kind" not in obj
                and all(isinstance(v, dict) for v in obj.values())):
            return {k: _norm_cost(v) for k, v in obj.items()}
    except json.JSONDecodeError:
        pass
    out = {}
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            ev = json.loads(line)
        except json.JSONDecodeError:
            continue
        if ev.get("kind") == "custom_call_cost" and "name" in ev:
            out[ev["name"]] = _norm_cost(ev)
    return out


def _import_bench_compare():
    import os

    try:
        import bench_compare
    except ImportError:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        import bench_compare
    return bench_compare


def ledger_totals(path):
    """Whole-module XLA totals from a compile-plane cost ledger (ISSUE 13):
    {"flops", "bytes_accessed", "peak_bytes", "rows", "partial_rows"}.
    Parsing (LAST row per executable key wins — a recompiled key
    supersedes its earlier rows) is ``bench_compare.load_ledger_file``,
    the one tool-side definition of a valid ledger row; keys whose
    backend reported nothing contribute null-safely and are counted in
    ``partial_rows``."""
    rows = _import_bench_compare().load_ledger_file(path)
    fl = [r["flops"] for r in rows.values() if r.get("flops") is not None]
    by = [r["bytes_accessed"] for r in rows.values()
          if r.get("bytes_accessed") is not None]
    pk = [r["peak_bytes"] for r in rows.values()
          if r.get("peak_bytes") is not None]
    return {"flops": sum(fl) if fl else None,
            "bytes_accessed": sum(by) if by else None,
            "peak_bytes": max(pk) if pk else None,
            "rows": len(rows),
            "partial_rows": sum(1 for r in rows.values() if r.get("partial"))}


def _import_pallas_kernels():
    """Import the kernel module whether invoked as `python tools/…` (script
    dir on sys.path, repo root not) or from an installed checkout."""
    import os

    try:
        from mxnet_tpu.ops import pallas_kernels as pk
    except ImportError:
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from mxnet_tpu.ops import pallas_kernels as pk
    return pk


def costs_live():
    pk = _import_pallas_kernels()
    return {k: _norm_cost(v) for k, v in pk.traced_costs().items()}


def registry_aliases():
    try:
        return _import_pallas_kernels().registered_custom_calls()
    except Exception:
        return {}


def match_cost(op_name, costs, aliases):
    """Case-insensitive substring match, both directions + aliases.

    Exact name wins outright; otherwise the LONGEST matching name/alias wins
    — dict order must not let "quantize_int8" claim a dequantize op, or a
    forward alias claim the backward kernel."""
    if op_name in costs:
        return op_name, costs[op_name]
    low = op_name.lower()
    best_name, best_score = None, 0
    for name in sorted(costs):
        cands = [name.lower()] + [a.lower() for a in aliases.get(name, ())]
        score = max((len(c) for c in cands if c in low or low in c),
                    default=0)
        if score > best_score:
            best_name, best_score = name, score
    if best_name is None:
        return None, None
    return best_name, costs[best_name]


def summarize(ops, costs, aliases, peak_flops, peak_bw):
    """→ list of row dicts sorted by total time desc, cost-only rows last."""
    rows, matched = [], set()
    for op, ent in ops.items():
        cname, cost = match_cost(op, costs, aliases)
        row = {"op": op, "calls": ent["calls"],
               "total_ms": ent["total_us"] / 1e3,
               "avg_us": ent["total_us"] / max(ent["calls"], 1),
               "flops": None, "bytes": None, "gflops_s": None, "gb_s": None,
               "intensity": None, "bound": None, "pct_roof": None,
               "cost_name": cname}
        if cost is not None:
            matched.add(cname)
            fl = cost["flops"] * ent["calls"]
            by = cost["bytes_accessed"] * ent["calls"]
            row["flops"], row["bytes"] = fl, by
            secs = ent["total_us"] / 1e6
            if secs > 0:
                row["gflops_s"] = fl / secs / 1e9
                row["gb_s"] = by / secs / 1e9
            if by > 0:
                inten = fl / by
                row["intensity"] = inten
                row["bound"] = ("compute" if inten > peak_flops / peak_bw
                                else "memory")
                attain = min(peak_flops, inten * peak_bw)
                if secs > 0:
                    # zero-flop ops: rate their achieved bandwidth instead
                    row["pct_roof"] = (100.0 * (fl / secs) / attain if fl
                                       else 100.0 * (by / secs) / peak_bw)
        rows.append(row)
    rows.sort(key=lambda r: -r["total_ms"])
    # registered costs with no device-time row: keep them visible
    for name, cost in sorted(costs.items()):
        if name in matched:
            continue
        inten = (cost["flops"] / cost["bytes_accessed"]
                 if cost["bytes_accessed"] else None)
        rows.append({"op": name, "calls": None, "total_ms": None,
                     "avg_us": None, "flops": cost["flops"],
                     "bytes": cost["bytes_accessed"], "gflops_s": None,
                     "gb_s": None, "intensity": inten,
                     "bound": (None if inten is None else
                               ("compute" if inten > peak_flops / peak_bw
                                else "memory")),
                     "pct_roof": None, "cost_name": name})
    return rows


def _fmt(v, spec="%.1f", dash="-"):
    return dash if v is None else spec % v


def render_table(rows, top=0):
    cols = ["op", "calls", "total_ms", "avg_us", "GFLOP", "MB",
            "GFLOP/s", "GB/s", "intens", "bound", "%roof"]
    table = [cols]
    shown = rows[:top] if top else rows
    for r in shown:
        table.append([
            r["op"][:48],
            _fmt(r["calls"], "%d"),
            _fmt(r["total_ms"], "%.3f"),
            _fmt(r["avg_us"], "%.1f"),
            _fmt(None if r["flops"] is None else r["flops"] / 1e9, "%.3f"),
            _fmt(None if r["bytes"] is None else r["bytes"] / 1e6, "%.2f"),
            _fmt(r["gflops_s"], "%.1f"),
            _fmt(r["gb_s"], "%.2f"),
            _fmt(r["intensity"], "%.2f"),
            r["bound"] or "-",
            _fmt(r["pct_roof"], "%.1f"),
        ])
    widths = [max(len(row[i]) for row in table) for i in range(len(cols))]
    lines = []
    for i, row in enumerate(table):
        lines.append("  ".join(
            c.ljust(widths[j]) if j == 0 else c.rjust(widths[j])
            for j, c in enumerate(row)))
        if i == 0:
            lines.append("  ".join("-" * w for w in widths))
    return "\n".join(lines)


def main(argv=None):
    p = argparse.ArgumentParser(
        description="per-op device-time + roofline table from a trace dump")
    p.add_argument("trace", nargs="+",
                   help="chrome-trace JSON (.json or .json.gz); several "
                        "per-rank files merge into one table")
    p.add_argument("--per-rank", action="store_true",
                   help="keep per-rank files apart (rows prefixed r<k>/) "
                        "instead of merging the ranks' events")
    p.add_argument("--costs", action="append", default=[],
                   help="cost table: telemetry JSONL or {name: {flops, "
                        "bytes_accessed}} JSON (repeatable)")
    p.add_argument("--xla-cost", default=None,
                   help="saved compile().cost_analysis() JSON for module-"
                        "level totals")
    p.add_argument("--ledger", default=None,
                   help="MXNET_COST_LEDGER JSONL (compile plane, ISSUE 13) "
                        "for module-level totals — supersedes --xla-cost, "
                        "no hand-saved cost JSON needed")
    p.add_argument("--live-registry", action="store_true",
                   help="also pull traced costs from the in-process Pallas "
                        "registry (imports jax)")
    p.add_argument("--device-kind", default=None,
                   help="device_kind of the chip that produced the trace; "
                        "its peaks come from the PEAKS table (known: %s)"
                        % ", ".join(sorted(PEAKS)))
    p.add_argument("--peak-flops", type=float, default=None,
                   help="roofline compute peak, FLOP/s (overrides the table)")
    p.add_argument("--peak-bw", type=float, default=None,
                   help="roofline HBM peak, B/s (overrides the table)")
    p.add_argument("--top", type=int, default=30,
                   help="show only the top-N ops by total time (0 = all)")
    p.add_argument("--json", action="store_true",
                   help="emit machine-readable JSON instead of the table")
    args = p.parse_args(argv)
    if args.device_kind is not None:
        if args.device_kind not in PEAKS:
            p.error("unknown --device-kind %r (known: %s); give --peak-flops "
                    "and --peak-bw" % (args.device_kind,
                                       ", ".join(sorted(PEAKS))))
        table = PEAKS[args.device_kind]
        if args.peak_flops is None:
            args.peak_flops = table[0]
        if args.peak_bw is None:
            args.peak_bw = table[1]
    if args.peak_flops is None or args.peak_bw is None:
        p.error("the roofline needs the chip's peaks: --device-kind, or both "
                "--peak-flops and --peak-bw")

    ops, costs, ranks = {}, {}, []
    for path in args.trace:
        try:
            events = load_trace(path)
        except (OSError, json.JSONDecodeError) as e:
            print("trace_summary: cannot read %s: %s" % (path, e),
                  file=sys.stderr)
            return 2
        rank = trace_rank(path, events)
        ranks.append(rank)
        prefix = ("r%d/" % rank) if args.per_rank and rank is not None \
            else ""
        aggregate_ops(events, ops=ops, prefix=prefix)
        costs.update(costs_from_trace(events))
    for path in args.costs:
        costs.update(costs_from_file(path))
    if args.live_registry:
        costs.update(costs_live())
    rows = summarize(ops, costs, registry_aliases(), args.peak_flops,
                     args.peak_bw)

    xla_totals = ledger_rows = None
    if args.ledger:
        try:
            lt = ledger_totals(args.ledger)
        except OSError as e:
            print("trace_summary: cannot read %s: %s" % (args.ledger, e),
                  file=sys.stderr)
            return 2
        xla_totals = {"flops": lt["flops"],
                      "bytes_accessed": lt["bytes_accessed"]}
        ledger_rows = lt
    elif args.xla_cost:
        with open(args.xla_cost, encoding="utf-8") as f:
            ca = json.load(f)
        xla_totals = {"flops": ca.get("flops"),
                      "bytes_accessed": ca.get("bytes accessed",
                                               ca.get("bytes_accessed"))}

    if args.json:
        print(json.dumps({"rows": rows, "xla_totals": xla_totals,
                          "ledger": ledger_rows,
                          "peak_flops": args.peak_flops,
                          "peak_bw": args.peak_bw,
                          "ranks": ranks}, indent=1))
        return 0

    total_ms = sum(r["total_ms"] or 0.0 for r in rows)
    print(render_table(rows, args.top))
    seen = sorted({r for r in ranks if r is not None})
    print("\n%d ops, %.3f ms total traced time; %d registered custom "
          "call(s)%s"
          % (sum(1 for r in rows if r["total_ms"] is not None), total_ms,
             len(costs),
             "" if not seen else "; ranks %s over %d file(s)"
             % (",".join(map(str, seen)), len(args.trace))))
    if ledger_rows is not None:
        print("cost ledger: %d executable(s), %d partial row(s)%s"
              % (ledger_rows["rows"], ledger_rows["partial_rows"],
                 "" if ledger_rows["peak_bytes"] is None else
                 "; peak executable %.1f MB"
                 % (ledger_rows["peak_bytes"] / 1e6)))
    if xla_totals and xla_totals["flops"] is not None:
        reg_fl = sum(r["flops"] or 0 for r in rows)
        print("XLA cost analysis: %.3f GFLOP module total; registered custom "
              "calls add %.3f GFLOP the analysis cannot see"
              % (xla_totals["flops"] / 1e9, reg_fl / 1e9))
    ridge = args.peak_flops / args.peak_bw
    print("roofline: peak %.1f TFLOP/s, %.1f GB/s, ridge intensity %.1f "
          "FLOP/B" % (args.peak_flops / 1e12, args.peak_bw / 1e9, ridge))
    return 0


if __name__ == "__main__":
    sys.exit(main())
