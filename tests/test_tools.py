"""Repo tools: the trace analyzer (tools/trace_analyze.py) against a
synthetic Chrome trace, the committed round-4 artifact, the serving
trace report (tools/trace_report.py), and the streamed-summary-record
schema guard (tools/check_stream_records.py)."""

import gzip
import json
import os
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

import trace_analyze  # noqa: E402


def _synthetic_trace(path, steps=4):
    """2 heavy ops x `steps` + one while wrapper, with metadata."""
    events = [
        {"ph": "M", "pid": 1, "tid": 7, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "X", "pid": 1, "tid": 7, "name": "while.1", "ts": 0,
         "dur": 4000 * steps,
         "args": {"hlo_category": "while"}},
    ]
    for i in range(steps):
        events.append({
            "ph": "X", "pid": 1, "tid": 7, "name": "fusion.1",
            "ts": 4000 * i, "dur": 3000,
            "args": {"hlo_category": "convolution fusion",
                     "model_flops": "6000000000",
                     "bytes_accessed": "1000000"}})
        events.append({
            "ph": "X", "pid": 1, "tid": 7, "name": "fusion.2",
            "ts": 4000 * i + 3000, "dur": 1000,
            "args": {"hlo_category": "loop fusion",
                     "model_flops": "0",
                     "bytes_accessed": "2000000"}})
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": events}, f)
    return path


def test_analyze_synthetic(tmp_path):
    path = _synthetic_trace(str(tmp_path / "t.trace.json.gz"), steps=4)
    res = trace_analyze.analyze(path)
    assert res["steps"] == 4                   # inferred modal count
    assert res["total_ms_per_step"] == pytest.approx(4.0)
    rows = {r["op"]: r for r in res["rows"]}
    conv = rows["fusion.1"]
    assert conv["ms_per_step"] == pytest.approx(3.0)
    assert conv["category"] == "convolution fusion"
    # 6 GFLOP in 3ms => 2 TF/s; 1 MB in 3ms => ~0.33 GB/s
    assert conv["tflops"] == pytest.approx(2.0)
    assert rows["fusion.2"]["gbps"] == pytest.approx(2.0)
    # the while wrapper is excluded from rows
    assert "while.1" not in rows


def test_analyze_committed_round4_artifact():
    """The committed AlexNet trace stays parseable and the PERF.md
    headline numbers stay reproducible from it."""
    path = os.path.join(REPO, "docs", "traces",
                        "alexnet_r4_step60ms.trace.json.gz")
    res = trace_analyze.analyze(path)
    assert res["steps"] == 8
    assert 40.0 < res["total_ms_per_step"] < 43.0       # 41.3 ms/step
    top = res["rows"][0]
    assert top["category"] == "convolution fusion"
    assert 3.5 < top["ms_per_step"] < 4.5


def test_check_stream_records_builtin_contract():
    """ISSUE 12 satellite, tier-1 (<30s): every streaming tool's
    summary_record — bench.py, lm_bench, chaos_bench, profile_ops,
    trace_report — carries the shared required keys even for the
    empty-results worst case, so a schema drift fails HERE instead of
    silently breaking whoever reads the stream."""
    import check_stream_records
    assert check_stream_records.check_builtin() == []


def test_check_stream_records_flags_bad_lines():
    import check_stream_records
    good = json.dumps({"metric": "m", "value": 1, "unit": "x",
                       "vs_baseline": None, "configs": {}})
    assert check_stream_records.check_line(good) == []
    # missing keys, non-JSON, empty metric, NaN all flagged
    assert check_stream_records.check_line(json.dumps({"metric": "m"}))
    assert check_stream_records.check_line("{not json")
    assert check_stream_records.check_line(json.dumps(
        {"metric": "", "value": 1, "unit": "x", "vs_baseline": None,
         "configs": {}}))
    nan = ('{"metric": "m", "value": NaN, "unit": "x", '
           '"vs_baseline": null, "configs": {}}')
    assert check_stream_records.check_line(nan)
    # a stream with one bad line among good ones names its line number
    problems = check_stream_records.check_stream(
        good + "\n" + "{broken\n" + good, "s")
    assert len(problems) == 1 and "s:2" in problems[0]


def test_trace_report_roundtrip(tmp_path, capsys):
    """tools/trace_report.py rebuilds per-request records from an
    exported Chrome trace: waterfall renders, ledger dedups batched
    dispatches, integrity check passes, and the streamed summary
    lines honor the shared record schema."""
    import check_stream_records
    import trace_report
    from veles_tpu.serving.tracing import SpanTracer
    tr = SpanTracer(mode="all", last=8)
    a = tr.start_request(rid="req-a", name="http.request", cat="http")
    b = tr.start_request(rid="req-b", name="http.request", cat="http")
    t = time.monotonic()
    tr.add_many([a, b], "decode.step", "decode", t, t + 0.004,
                attrs={"backend": "xla", "bucket": 4})
    tr.add_many([a], "prefill.chunk", "prefill", t, t + 0.002,
                attrs={"backend": "xla", "bucket": 8})
    tr.finish_request(a)
    tr.finish_request(b, error=RuntimeError("boom"))
    path = str(tmp_path / "serve.trace.json")
    with open(path, "w") as f:
        json.dump(tr.export_chrome(), f)
    rc = trace_report.main([path, "--all", "--check",
                            "--ledger-json",
                            str(tmp_path / "ledger.json")])
    assert rc == 0
    out = capsys.readouterr()
    # stdout lines are all schema-conforming records, last-line-wins
    assert check_stream_records.check_stream(out.out) == []
    last = json.loads(out.out.strip().splitlines()[-1])
    assert last["metric"] == "trace_ledger_dispatches"
    # the 2-lane decode.step dedups to ONE dispatch + one prefill
    assert last["value"] == 2
    assert last["configs"]["requests"] == 2
    assert last["configs"]["errored"] == 1
    # waterfalls went to stderr for both requests (req-b also shows
    # up once more as the auto-dump log line from finish_request)
    assert "request req-a" in out.err and "request req-b" in out.err
    ledger = json.load(open(str(tmp_path / "ledger.json")))["ledger"]
    by_op = {r["op"]: r for r in ledger}
    assert by_op["decode.step"]["dispatches"] == 1
    assert by_op["decode.step"]["lanes"] == 2


def test_trace_report_unknown_request_errors(tmp_path, capsys):
    import trace_report
    from veles_tpu.serving.tracing import SpanTracer
    tr = SpanTracer(mode="all")
    tr.finish_request(tr.start_request(rid="only"))
    path = str(tmp_path / "t.json")
    with open(path, "w") as f:
        json.dump(tr.export_chrome(), f)
    assert trace_report.main([path, "--request", "nope"]) == 1
    capsys.readouterr()
