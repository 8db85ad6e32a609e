"""Map a jax.profiler trace back onto the named solver sections.

The measured half of the performance-attribution layer (round 7; the
modeled half is ``fdtd3d_tpu/costs.py``). Point it at the directory a
capture wrote (CLI ``--profile DIR``, bench ``FDTD3D_BENCH_PROFILE``,
or ``jax.profiler.trace``):

    python tools/trace_attribution.py DIR [--ledger LEDGER.json]
        [--json] [--out attribution.jsonl]

It parses the trace-viewer JSON (``*.trace.json[.gz]`` under
``plugins/profile/<session>/``), sums the duration of every event whose
name or args carry a ``fdtd3d/<section>`` scope — host
``TraceAnnotation`` spans and device op events that carry the
``jax.named_scope`` stack — and reports measured time per section
(innermost scope wins, matching the cost ledger's attribution rule).
On a TPU v5 lite the stack is an op event's ``tf_op`` arg, and not
every op event has one (two thirds of them had none in a 512^3 Mie
window); a fusion's names only its root op, and XLA:CPU op events
carry none.
``telemetry.op_scopes()`` names every op of the chunk executable
(``jit_fdtd3d_chunk``) from its optimised HLO, fused parts included,
and ``telemetry.span_totals()`` counts the host spans, set-up ones
(``sim-init``, ``build-coeffs`` ...) included, with no capture at all
(docs/OBSERVABILITY.md). With ``--ledger`` the modeled shares sit next to the
measured ones in a single merged artifact: one telemetry schema-v2
``attribution`` record, validated by ``telemetry.validate_record``.

Round 10 (comm observability): multi-core captures additionally get
PER-CORE device timelines — each trace process whose name looks like a
device core (``/device:TPU:N``, ``TPU:N``, ``... Core N``) keeps its
own per-section sums — merged into the same record as ``per_core``
(per-core section tables + totals) and ``imbalance`` (max/mean ratio
of per-core totals and the named top-straggler core). A single-core or
host-only capture simply omits both keys.

Degrades cleanly: a directory with no trace files (capture skipped —
no chip, no profiler) reports that and exits 0 with no artifact.

This is the one blessed way to decompose step time; the round-3/4
sweep scripts (tools/measure_r3.py / measure_r4.py) are legacy.
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # repo root for fdtd3d_tpu

from fdtd3d_tpu import telemetry  # noqa: E402
from fdtd3d_tpu.log import report, warn  # noqa: E402

_SCOPE_RE = re.compile(r"fdtd3d/([\w-]+)")


def find_trace_files(path: str) -> List[str]:
    """Trace-viewer JSON files under a capture dir (or the file itself),
    newest profiler session first."""
    if os.path.isfile(path):
        return [path]
    hits: List[str] = []
    for pat in ("*.trace.json.gz", "*.trace.json"):
        hits += glob.glob(os.path.join(path, "**", pat), recursive=True)
    # newest session dir first (a dir may hold several captures)
    return sorted(hits, key=os.path.getmtime, reverse=True)


def _load_events(path: str) -> List[Dict[str, Any]]:
    op = gzip.open if path.endswith(".gz") else open
    with op(path, "rt") as f:
        return json.load(f).get("traceEvents", [])


def _event_sections(ev: Dict[str, Any]) -> Optional[str]:
    """Innermost fdtd3d/<name> scope mentioned by an event, if any."""
    hay = ev.get("name", "")
    args = ev.get("args")
    if args:
        hay += " " + " ".join(str(v) for v in args.values())
    last = None
    for m in _SCOPE_RE.finditer(hay):
        last = m.group(1)
    return last


def attribute_events(events) -> Tuple[Dict[str, float], Dict[str, float]]:
    """-> (graph_sections_ms, host_spans_ms) summed over complete
    ('X'-phase) events; nested graph scopes resolve innermost-first
    exactly like the cost ledger, host spans keep their own table."""
    graph: Dict[str, float] = {}
    host: Dict[str, float] = {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        sec = _event_sections(ev)
        if sec is None:
            continue
        dur_ms = float(ev.get("dur", 0.0)) / 1e3
        if sec in telemetry.GRAPH_SPANS:
            graph[sec] = graph.get(sec, 0.0) + dur_ms
        elif sec in telemetry.HOST_SPANS:
            host[sec] = host.get(sec, 0.0) + dur_ms
    return graph, host


# device-core process names as trace viewers emit them: jax/XProf
# exports "/device:TPU:0"; raw xplane conversions show "TPU:0" or
# "... Chip 0 ... Core 1" variants (chip AND core must both survive —
# collapsing "Chip 0 Core 0" and "Chip 1 Core 0" into one key would
# merge two devices' timelines). Host processes (python, threads)
# match none and stay out of the per-core lane.
_CORE_RES = (re.compile(r"/device:([A-Za-z]+:\d+)"),
             re.compile(r"\b(TPU:\d+)\b"),
             re.compile(r"\b[Cc]hip\s*(\d+)\b.*\b[Cc]ore\s*(\d+)\b"),
             re.compile(r"\b[Cc]ore\s*(\d+)\b"))


def _core_of(process_name: str) -> Optional[str]:
    for rx in _CORE_RES:
        m = rx.search(process_name or "")
        if not m:
            continue
        if len(m.groups()) == 2:
            return f"chip{m.group(1)}-core{m.group(2)}"
        g = m.group(1)
        return g if ":" in g else f"core:{g}"
    return None


def attribute_events_per_core(events) -> Dict[str, Dict[str, float]]:
    """Per-CORE graph-section sums: {core: {section: ms}}.

    Core identity comes from the trace's process_name metadata
    (ph=='M') — only pids whose name looks like a device core
    participate; host-side spans never pollute a core's timeline."""
    pid_core: Dict[Any, str] = {}
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            core = _core_of((ev.get("args") or {}).get("name", ""))
            if core is not None:
                pid_core[ev.get("pid")] = core
    out: Dict[str, Dict[str, float]] = {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        core = pid_core.get(ev.get("pid"))
        if core is None:
            continue
        sec = _event_sections(ev)
        if sec is None or sec not in telemetry.GRAPH_SPANS:
            continue
        dur_ms = float(ev.get("dur", 0.0)) / 1e3
        tbl = out.setdefault(core, {})
        tbl[sec] = tbl.get(sec, 0.0) + dur_ms
    return out


def core_imbalance(per_core: Dict[str, Dict[str, float]]
                   ) -> Optional[Dict[str, Any]]:
    """Straggler attribution over per-core TOTAL section time: max and
    mean total, their ratio, and the named top-straggler core. None
    below two cores (nothing to compare)."""
    if len(per_core) < 2:
        return None
    totals = {core: sum(tbl.values()) for core, tbl in per_core.items()}
    straggler = max(totals, key=totals.get)
    mx = totals[straggler]
    mean = sum(totals.values()) / len(totals)
    return {
        "max_ms": round(mx, 4),
        "mean_ms": round(mean, 4),
        "ratio": round(mx / mean, 4) if mean > 0 else None,
        "straggler": straggler,
        "n_cores": len(per_core),
    }


def merge_with_ledger(graph_ms: Dict[str, float],
                      host_ms: Dict[str, float],
                      ledger: Optional[Dict[str, Any]],
                      source: str,
                      per_core: Optional[Dict[str, Dict[str, float]]]
                      = None) -> Dict[str, Any]:
    """One merged measured-vs-modeled attribution artifact (telemetry
    schema-v2 'attribution' record; multi-core captures add the
    per_core tables + imbalance straggler summary)."""
    total = sum(graph_ms.values())
    sections: Dict[str, Any] = {}
    names = set(graph_ms)
    modeled = (ledger or {}).get("sections", {})
    names |= set(modeled)
    for name in sorted(names):
        row: Dict[str, Any] = {}
        if name in graph_ms:
            row["measured_ms"] = round(graph_ms[name], 4)
            row["measured_frac"] = round(graph_ms[name] / total, 4) \
                if total > 0 else 0.0
        if name in modeled:
            row["modeled_flops_frac"] = modeled[name]["flops_frac"]
            row["modeled_bytes_frac"] = modeled[name]["bytes_frac"]
        sections[name] = row
    rec = {
        "v": telemetry.SCHEMA_VERSION,
        "type": "attribution",
        "source": source,
        "sections": sections,
        "measured_total_ms": round(total, 4) if graph_ms else None,
        "coverage_bytes": (ledger or {}).get("per_step", {}).get(
            "coverage_bytes"),
    }
    if host_ms:
        rec["host_spans_ms"] = {k: round(v, 4)
                                for k, v in sorted(host_ms.items())}
    if per_core:
        rec["per_core"] = {
            core: {"sections": {k: round(v, 4)
                                for k, v in sorted(tbl.items())},
                   "total_ms": round(sum(tbl.values()), 4)}
            for core, tbl in sorted(per_core.items())}
        imb = core_imbalance(per_core)
        if imb is not None:
            rec["imbalance"] = imb
    if ledger is not None:
        rec["ledger_step_kind"] = ledger.get("step_kind")
        if ledger.get("roofline"):
            rec["roofline"] = ledger["roofline"]
    telemetry.validate_record(rec)
    return rec


def format_text(rec: Dict[str, Any]) -> str:
    lines = [f"attribution: {rec['source']}"]
    total = rec.get("measured_total_ms")
    if total is not None:
        lines.append(f"  measured section time: {total:.3f} ms total")
    for name, row in rec["sections"].items():
        bits = []
        if "measured_ms" in row:
            bits.append(f"measured {row['measured_ms']:.3f} ms "
                        f"({row['measured_frac']:.1%})")
        if "modeled_bytes_frac" in row:
            bits.append(f"modeled bytes {row['modeled_bytes_frac']:.1%}"
                        f" / flops {row['modeled_flops_frac']:.1%}")
        lines.append(f"  {name:16s} " + "; ".join(bits))
    for k, v in (rec.get("host_spans_ms") or {}).items():
        lines.append(f"  [host] {k:16s} {v:.3f} ms")
    for core, row in (rec.get("per_core") or {}).items():
        lines.append(f"  [core] {core:12s} total {row['total_ms']:.3f}"
                     f" ms  " + "; ".join(
                         f"{s} {v:.3f}" for s, v in
                         row["sections"].items()))
    if rec.get("imbalance"):
        im = rec["imbalance"]
        lines.append(f"  imbalance: max/mean "
                     f"{im['ratio'] if im['ratio'] is not None else '?'}"
                     f" over {im['n_cores']} cores — top straggler "
                     f"{im['straggler']} ({im['max_ms']:.3f} ms vs mean "
                     f"{im['mean_ms']:.3f} ms)")
    if rec.get("roofline"):
        r = rec["roofline"]
        lines.append(f"  roofline: {r['hbm_gbps']:.1f} GB/s -> modeled "
                     f"{r['modeled_mcells_per_s']:.1f} Mcells/s "
                     f"({r['modeled_step_ms']:.3f} ms/step)")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="attribute a jax.profiler trace to the named "
                    "fdtd3d solver sections (merge with a cost ledger "
                    "via --ledger)")
    ap.add_argument("trace", help="capture directory (CLI --profile "
                                  "DIR) or one *.trace.json[.gz]")
    ap.add_argument("--ledger", metavar="PATH", default=None,
                    help="cost ledger JSON (fdtd3d_tpu.costs) to merge "
                         "modeled shares into the artifact")
    ap.add_argument("--json", action="store_true",
                    help="emit the attribution record as JSON")
    ap.add_argument("--out", metavar="PATH", default=None,
                    help="append the validated attribution record to "
                         "this JSONL file")
    args = ap.parse_args(argv)

    files = find_trace_files(args.trace)
    ledger = None
    if args.ledger:
        from fdtd3d_tpu import costs
        with open(args.ledger) as f:
            ledger = json.load(f)
        costs.validate_ledger(ledger)
    if not files:
        # clean skip, no partial artifact: the capture itself degraded
        # (no chip / profiler) or the path is empty
        report(f"no trace files under {args.trace!r} (capture skipped "
               f"or not yet finalized); nothing to attribute")
        return 0
    events = _load_events(files[0])
    graph_ms, host_ms = attribute_events(events)
    if not graph_ms and not host_ms:
        warn(f"{files[0]}: no fdtd3d/* events found — trace predates "
             f"the named spans, or the device lane carries no HLO "
             f"metadata on this backend (host spans require a capture "
             f"around Simulation.advance)")
    per_core = attribute_events_per_core(events)
    rec = merge_with_ledger(graph_ms, host_ms, ledger, files[0],
                            per_core=per_core)
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
    report(json.dumps(rec) if args.json else format_text(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
