"""Streamed summary-record schema guard (ISSUE 12 satellite).

Every bench in this repo streams one ``summary_record`` JSON line to
stdout after each completed leg — ``bench.py``, ``tools/lm_bench.py``,
``tools/chaos_bench.py``, ``tools/profile_ops.py``,
``tools/trace_report.py`` — and the driver (plus ``bench_report.py``
and the TPU-session tooling) parses the LAST line, so a silent schema
drift in any one tool breaks evidence collection without failing
anything.  This checker makes the shared contract executable:

- REQUIRED KEYS: every record carries ``metric`` (str), ``value``,
  ``unit``, ``vs_baseline`` and ``configs`` — exactly the bench.py
  shape.
- JSON-CLEAN: the record round-trips through ``json.dumps`` (no numpy
  scalars, no NaN/Infinity — strict parsers reject them).

Two modes:

- BUILTIN (default, <30s, rides tier-1 via ``tests/test_tools.py``):
  import each tool and validate the record its ``summary_record``
  produces for an EMPTY results dict — the worst-case partial stream a
  watchdog kill can leave — plus ``profile_ops``'s streamed line.
- FILE (``--file runs.jsonl``): validate every line of a captured
  stream (a bench's stdout), so a real run's records can be audited
  after the fact.

Exit 0 when every record conforms; 1 with one problem per line
otherwise.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys

TOOLS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TOOLS_DIR)
sys.path.insert(0, TOOLS_DIR)
sys.path.insert(0, REPO)

#: the shared record contract every streamed summary line honors
REQUIRED_KEYS = ("metric", "value", "unit", "vs_baseline", "configs")

#: the ``GET /timeseries.json`` payload contract (ISSUE 14) — what
#: tools/slo_report.py and the TPU-session tooling join on
TIMESERIES_KEYS = ("name", "sampled_at", "interval_s", "window_s",
                   "samples", "series")
#: the ``GET /slo.json`` payload contract
SLO_KEYS = ("name", "sampled_at", "windows_s", "worst_state",
            "worst_state_name", "pages_total", "objectives")
#: every objective row in /slo.json ("held" = the state was carried
#: by the min_events gate rather than computed from fresh evidence)
SLO_OBJECTIVE_KEYS = ("source", "objective", "kind", "target",
                      "state", "state_name", "held", "burn_rates")


def check_payload(payload, required, where):
    """Problems with one endpoint payload: required keys + strict
    JSON (the shared shape rule, applied to the ISSUE 14 endpoints)."""
    problems = []
    if not isinstance(payload, dict):
        return ["%s: not a JSON object (got %s)"
                % (where, type(payload).__name__)]
    for key in required:
        if key not in payload:
            problems.append("%s: missing required key %r"
                            % (where, key))
    try:
        json.loads(json.dumps(payload, allow_nan=False))
    except (TypeError, ValueError) as e:
        problems.append("%s: not strict-JSON-serializable: %s"
                        % (where, e))
    return problems


def check_timeseries_payload(payload, where="timeseries.json"):
    """The /timeseries.json shape: top-level keys, and every series
    row carries a known kind with that kind's windowed fields."""
    problems = check_payload(payload, TIMESERIES_KEYS, where)
    for name, row in (payload.get("series") or {}).items():
        w = "%s series %r" % (where, name)
        kind = row.get("kind")
        if kind == "counter":
            need = ("last", "delta", "rate_per_s", "span_s")
        elif kind == "gauge":
            need = ("last", "min", "max", "mean")
        elif kind == "hist":
            need = ("count_delta", "rate_per_s", "p50", "p95",
                    "bounds")
        else:
            problems.append("%s: unknown kind %r" % (w, kind))
            continue
        for key in need:
            if key not in row:
                problems.append("%s: %s row missing %r"
                                % (w, kind, key))
    return problems


def check_slo_payload(payload, where="slo.json"):
    problems = check_payload(payload, SLO_KEYS, where)
    for row in (payload.get("objectives") or []):
        w = "%s objective %r" % (where, row.get("objective"))
        for key in SLO_OBJECTIVE_KEYS:
            if key not in row:
                problems.append("%s: missing %r" % (w, key))
        for b in row.get("burn_rates", []):
            for key in ("window_s", "burn", "error_ratio", "events"):
                if key not in b:
                    problems.append("%s: burn row missing %r"
                                    % (w, key))
    return problems


def _builtin_payload_problems():
    """Exercise the ISSUE 14 payload shapes against LIVE producers: a
    tiny in-process TimeSeriesStore + SLOMonitor (no jax, <1s), so a
    schema drift in either endpoint fails tier-1 loudly."""
    from veles_tpu.serving.metrics import ServingMetrics
    from veles_tpu.serving.slo import SLOMonitor
    from veles_tpu.serving.timeseries import TimeSeriesStore
    m = ServingMetrics("schema_probe")
    store = TimeSeriesStore(interval_s=0.05, capacity=16)
    store.add_source(m)
    problems = []
    for i in range(3):
        m.record_enqueue()
        m.record_response(0.004 * (i + 1))
        m.record_ttft(0.01)
        m.record_decode_step(0.002)
        m.set_gauge("queue_depth", i)
        store.sample_once()
    problems.extend(check_timeseries_payload(
        store.snapshot(window_s=60.0),
        "TimeSeriesStore.snapshot()"))
    monitor = SLOMonitor(store, SLOMonitor.default_objectives(),
                         windows_s=(5.0, 30.0), min_events=1)
    monitor.sample_once()
    problems.extend(check_slo_payload(monitor.snapshot(),
                                      "SLOMonitor.snapshot()"))
    return problems


def check_record(record, where="record"):
    """Problems with one parsed record (empty list = conforming)."""
    problems = []
    if not isinstance(record, dict):
        return ["%s: not a JSON object (got %s)"
                % (where, type(record).__name__)]
    for key in REQUIRED_KEYS:
        if key not in record:
            problems.append("%s: missing required key %r" % (where, key))
    metric = record.get("metric")
    if "metric" in record and (not isinstance(metric, str) or not metric):
        problems.append("%s: metric must be a non-empty string (got %r)"
                        % (where, metric))
    try:
        # strict JSON: numpy scalars and NaN/Infinity both die here,
        # which is exactly what a downstream strict parser would do
        json.loads(json.dumps(record, allow_nan=False))
    except (TypeError, ValueError) as e:
        problems.append("%s: not strict-JSON-serializable: %s"
                        % (where, e))
    return problems


def check_line(line, where="line"):
    """Problems with one raw stream line."""
    line = line.strip()
    if not line:
        return []
    try:
        record = json.loads(line)
    except json.JSONDecodeError as e:
        return ["%s: does not parse as JSON: %s" % (where, e)]
    return check_record(record, where)


def check_stream(text, where="stream"):
    problems = []
    for i, line in enumerate(text.splitlines(), start=1):
        problems.extend(check_line(line, "%s:%d" % (where, i)))
    return problems


def _builtin_records():
    """(where, record) pairs from every streaming tool's
    summary-record builder, fed the empty-results worst case (what a
    watchdog kill right after startup leaves) — importing the tool IS
    part of the check (an ImportError is a failed record source)."""
    out = []

    import bench
    out.append(("bench.summary_record({})", bench.summary_record({})[0]))

    import chaos_bench
    import lm_bench
    import trace_report
    out.append(("lm_bench.summary_record({})",
                lm_bench.summary_record({})[0]))
    # the megastep record path (ISSUE 13): a headline carrying the
    # fused-decode column must select the lm_megastep_* metric and
    # still conform to the shared schema
    ms_record = lm_bench.summary_record({
        "headline": {
            "dispatches_per_token_megastep_single_lane": 0.062}})[0]
    out.append(("lm_bench.summary_record(megastep headline)",
                ms_record))
    if ms_record.get("metric") != "lm_megastep_dispatches_per_token":
        out.append(("lm_bench.summary_record(megastep headline)",
                    {"metric": "",
                     "note": "megastep headline did not select the "
                             "lm_megastep_dispatches_per_token metric"}))
    out.append(("chaos_bench.summary_record({})",
                chaos_bench.summary_record({})[0]))
    out.append(("trace_report.summary_record({})",
                trace_report.summary_record({})[0]))

    out.extend(_lint_records())

    import slo_report
    out.append(("slo_report.summary_record({})",
                slo_report.summary_record({})[0]))
    # the verdict-bearing shape must select the paging-objective
    # metric (the acceptance signal downstream tooling keys on)
    slo_rec = slo_report.summary_record(
        {"verdicts": [{"state_name": "page"}]})[0]
    out.append(("slo_report.summary_record(verdicts)", slo_rec))
    if slo_rec.get("metric") != "slo_objectives_paging":
        out.append(("slo_report.summary_record(verdicts)",
                    {"metric": "",
                     "note": "verdict results did not select the "
                             "slo_objectives_paging metric"}))

    # profile_ops streams directly — capture its line
    import profile_ops
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        profile_ops.stream_summary()
    line = buf.getvalue().strip().splitlines()[-1]
    out.append(("profile_ops.stream_summary()", json.loads(line)))
    return out


def _lint_records():
    """veles_lint's streamed records (ISSUE 15/17): the empty-results
    worst case, a populated run, and both faces of the bench-leg
    ``lint_clean`` record (lm_bench/chaos_bench stream it before
    their first real leg) — no jax import, <1s."""
    import veles_lint
    return [
        ("veles_lint.summary_record({})",
         veles_lint.summary_record({})[0]),
        ("veles_lint.summary_record(populated)",
         veles_lint.summary_record(
             {"findings": 2, "stats": {"files": 11,
                                       "suppressions": 3}})[0]),
        ("veles_lint.clean_record(clean)",
         veles_lint.clean_record(0, {"files": 11, "wall_s": 0.5})[0]),
        ("veles_lint.clean_record(dirty)",
         veles_lint.clean_record(3, {"files": 11, "wall_s": 0.5})[0]),
    ]


#: tools checkable WITHOUT importing the jax-heavy benches — the <1s
#: ``--tool`` mode (tests/test_lint.py rides it)
FAST_TOOLS = {"veles_lint": _lint_records}


def check_tool(name):
    """Validate one fast tool's records only (no bench imports);
    returns problems."""
    if name not in FAST_TOOLS:
        return ["unknown fast tool %r (one of %r)"
                % (name, sorted(FAST_TOOLS))]
    problems = []
    try:
        records = FAST_TOOLS[name]()
    except Exception as e:   # noqa: BLE001 — an unimportable tool IS
        return ["collecting %s records failed: %s: %s"
                % (name, type(e).__name__, e)]
    for where, record in records:
        problems.extend(check_record(record, where))
    return problems


def check_builtin():
    """Validate every tool's empty-results record; returns problems."""
    problems = []
    try:
        records = _builtin_records()
    except Exception as e:   # noqa: BLE001 — an unimportable tool IS
        return ["collecting builtin records failed: %s: %s"
                % (type(e).__name__, e)]
    for where, record in records:
        problems.extend(check_record(record, where))
    try:
        problems.extend(_builtin_payload_problems())
    except Exception as e:   # noqa: BLE001 — a broken producer IS
        problems.append("collecting builtin payloads failed: %s: %s"
                        % (type(e).__name__, e))
    return problems


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--file", default=None, metavar="JSONL",
                        help="validate every line of this captured "
                             "stream instead of the builtin tool check")
    parser.add_argument("--tool", default=None, metavar="NAME",
                        help="validate only this fast tool's records "
                             "(no bench imports, <1s): one of %s"
                             % sorted(FAST_TOOLS))
    args = parser.parse_args(argv)
    if args.file:
        with open(args.file, "r", encoding="utf-8") as f:
            problems = check_stream(f.read(), args.file)
        checked = "stream %s" % args.file
    elif args.tool:
        problems = check_tool(args.tool)
        checked = "fast tool %s" % args.tool
    else:
        problems = check_builtin()
        checked = "builtin summary_record sources"
    for p in problems:
        print("PROBLEM: %s" % p, file=sys.stderr)
    print(json.dumps({"checked": checked,
                      "problems": len(problems)}))
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
