"""The program's record of each device dispatch, read by the benchmark: the
dispatch ring of ``veles_tpu/serving/tracing.py::LoopRecorder`` (ISSUE 38; one
row per call of a jitted program by the engine's worker thread: the program,
the turn that made the call, and the stamps of the call, of its return, of the
moment the host began to wait for the outputs and of the moment it had them;
``DCOL_*``), put on the clock of the device trace by pairing the trace's
executions with those rows BY PROGRAM AND ORDER, whatever turn a fetch falls
in, however many dispatches a turn makes and whatever their programs are
called.  ``lib/spans.py`` rebuilds the same sequence from the turn rows and so
holds only while a turn is "its chunk, then its step, fetched in the same
row"; both stand side by side until a ``benchmark`` issue retires one.

**One clock** (``fit``): ``spans.fit``'s causality over other calls.  Call k
is ``(program, DCOL_CALL, DCOL_FETCHED)`` in ``DCOL_SEQ`` order, which is the
order the device runs them; ``d = T_device - T_monotonic`` lies under
``start_k - call_k`` for every pair and over ``end_k - fetched_k`` wherever the
host fetched that dispatch's outputs; the one alignment of the trace's
executions on the ring's rows that leaves an interval is taken, and its middle.
The interval's width (the slack) is the smallest launch delay plus the smallest
return delay of the trace: no run can say how it divides between the two.

**The split** (``parts``): an idle gap of the first device between the end of
one execution's last operation and the start of the next execution's first
is, on the host's clock,

- ``return``: the part in which the host was between ``DCOL_WAIT`` and
  ``DCOL_FETCHED`` of a dispatch whose execution had ended: the device has
  finished and the host does not know it yet;
- ``launch``: the part after ``DCOL_CALL`` of the dispatch that ends the gap:
  the jit call's own host time (to ``DCOL_RETURNED``), then the runtime's
  launch;
- ``host``: the rest: the host had what it waited for and had not made the
  next call (puts, guards, the tick).

What the offset's slack moves: a gap that begins where the host waited for
the execution before it and ends behind a call has its ``host`` between two
stamps of the host (the fetch, the call), so ``host`` and ``return + launch``
do not move with the offset: THOSE TWO are measured.  How ``return + launch``
divides is the midpoint's choice: each such gap gives up to half the slack to
each, so the two read alike whatever the truth is.  (A gap behind an execution
nobody waited for, a chunk that is no tail, begins at a stamp of the device,
and there ``host`` moves as well.)  ``report`` prints all three, and the
tokens' way back, at the two ends of the interval; nothing is to be concluded
from return against launch until something stamps the device's side.

Gaps between the operations INSIDE one execution are a fourth sum, printed and
in none of the three.  A program whose tracing has no dispatch ring (the
parent of the PR that brought it) gives None everywhere."""

from __future__ import annotations

import collections

import numpy

from benchmark.lib import spans, trace as trace_lib
from benchmark.lib.spans import bounds, executions, recorder, say, window_ns

PARTS = ("return", "launch", "host")


def rows(art):
    """{"rows": the dispatch ring's copy (int64, ``DCOL_*``), "tracing",
    "programs"} of the recorder that served the window, or None where the
    program keeps no dispatch ring."""
    if "_dispatch_log_rows" in art:
        return art["_dispatch_log_rows"]
    art["_dispatch_log_rows"] = None
    found = recorder(art)
    if found is None or not hasattr(found["tracing"], "DCOL_SEQ") \
            or not hasattr(found["recorder"], "dispatches"):
        return None
    art["_dispatch_log_rows"] = {
        "rows": found["recorder"].dispatches(), "tracing": found["tracing"],
        "programs": found["recorder"].programs}
    return art["_dispatch_log_rows"]


def jit_call_ms(art):
    """{"step", "chunk"}: median ms of ``DCOL_RETURNED - DCOL_CALL`` over the
    window's decode dispatches and over its chunks (None for a kind the
    window has none of): the host's time inside the jit call.  The recorder
    alone, no trace."""
    log = rows(art)
    if log is None:
        return None
    t, r = log["tracing"], log["rows"]
    lo, hi = window_ns(art)
    r = r[(r[:, t.DCOL_CALL] >= lo) & (r[:, t.DCOL_RETURNED] <= hi)
          & (r[:, t.DCOL_RETURNED] > 0)]
    out = {}
    for kind, phase in (("step", t.STEP_DISPATCH),
                        ("chunk", t.PREFILL_DISPATCH)):
        of = r[r[:, t.DCOL_PHASE] == phase]
        out[kind] = float(numpy.median(
            of[:, t.DCOL_RETURNED] - of[:, t.DCOL_CALL])) / 1e6 \
            if len(of) else None
    if out["step"] is not None:
        say("dispatch log: jit call p50 %.3f ms a decode dispatch, %s a chunk"
            % (out["step"], "none" if out["chunk"] is None
               else "%.3f ms" % out["chunk"]))
    return out


def fit(art, margin_s=2.0):
    """{"offset", "slack", "execs": [(program, start, end)], "rows": the
    dispatch rows paired with them, one each, "tracing": the module that
    names their columns} with ``offset`` the middle of the interval
    (``offset - slack / 2`` to ``offset + slack / 2``) that causality leaves
    for ``T_device - T_monotonic``; None
    without a dispatch ring or a trace, or where not exactly one alignment
    of the executions on the ring's rows satisfies causality."""
    if "_dispatch_log_fit" in art:
        return art["_dispatch_log_fit"]
    art["_dispatch_log_fit"] = None
    log = rows(art)
    trace = art.get("trace")
    if log is None or not trace or not trace["devices"] \
            or not art.get("trace_host_window"):
        return None
    t, programs = log["tracing"], log["programs"]
    execs = executions(trace, programs)
    begin, end = (int(x * 1e9) for x in art["trace_host_window"])
    margin = int(margin_s * 1e9)
    near = log["rows"]
    near = near[(near[:, t.DCOL_CALL] >= begin - margin)
                & (near[:, t.DCOL_CALL] <= end + margin)]
    calls = [(programs[row[t.DCOL_PROGRAM]], row[t.DCOL_CALL],
              row[t.DCOL_FETCHED]) for row in near.tolist()]
    if len(execs) < 2 or len(calls) < len(execs):
        say("dispatch log: %d executions in the trace, %d dispatch records "
            "around its window: nothing to match" % (len(execs), len(calls)))
        return None
    feasible = []
    for k in range(len(calls) - len(execs) + 1):
        b = bounds(execs, calls[k:k + len(execs)])
        if b is None or b[0] > b[1]:
            continue
        d = (b[0] + b[1]) / 2.0
        # the traced executions lie inside the host's traced window
        if execs[0][1] - d < begin - margin / 4 \
                or execs[-1][2] - d > end + margin / 4:
            continue
        feasible.append((k, b))
    if len(feasible) != 1:
        say("dispatch log: %d alignments of %d executions on %d dispatch "
            "records satisfy causality (want exactly 1): no mapping"
            % (len(feasible), len(execs), len(calls)))
        return None
    k, (lo, hi) = feasible[0]
    art["_dispatch_log_fit"] = {
        "offset": (lo + hi) / 2.0, "slack": hi - lo, "execs": execs,
        "rows": near[k:k + len(execs)], "tracing": t}
    say("dispatch log: clock fit slack %.1f us over %d executions paired "
        "with dispatch records %d to %d"
        % ((hi - lo) / 1e3, len(execs), near[k, t.DCOL_SEQ],
           near[k + len(execs) - 1, t.DCOL_SEQ]))
    other = spans.fit(art)
    if other is not None:
        say("dispatch log: offset less the turn-order fit's %.1f us (that "
            "fit's slack %.1f us)"
            % ((art["_dispatch_log_fit"]["offset"] - other["offset"]) / 1e3,
               other["slack"] / 1e3))
    return art["_dispatch_log_fit"]


def matched_share(art):
    """Share (%) of the first device's executions, of ANY program, from the
    first to the last that the fit paired, that have a dispatch record.  The
    rest is what the engine sends to the device outside
    ``LoopRecorder.dispatch`` (a copy of a page, a program the recorder was
    never told of): it runs with no row, the gap before it has no launch
    part, and it is named on standard error.  None without a fit."""
    fitted = fit(art)
    if fitted is None:
        return None
    execs = fitted["execs"]
    known = {program for program, _, _ in execs}
    known |= {"jit_" + program for program in known}
    among = [m.name for m in art["trace"]["devices"][0]["modules"]
             if execs[0][1] <= m.start <= execs[-1][1]]
    unrecorded = collections.Counter(n for n in among if n not in known)
    if unrecorded:
        say("dispatch log: executions with no dispatch record: "
            + ", ".join("%s %d" % of for of in unrecorded.most_common()))
    return 100.0 * len(execs) / len(among)


def token_return_ms(art):
    """Median ms, over the traced decode dispatches, from the end of the
    execution on the host's clock to ``DCOL_FETCHED``: how long the outputs
    of a finished step take to reach the host.  A latency: it stays when a
    later fetch takes the idle time away.  Read at the interval's middle, so
    known to half the slack either way (both ends are printed)."""
    fitted = fit(art)
    if fitted is None:
        return None
    t, r = fitted["tracing"], fitted["rows"]
    ends = numpy.array([e for _, _, e in fitted["execs"]]) - fitted["offset"]
    of = (r[:, t.DCOL_PHASE] == t.STEP_DISPATCH) & (r[:, t.DCOL_FETCHED] > 0)
    if not of.any():
        return None
    found = float(numpy.median(r[of, t.DCOL_FETCHED] - ends[of])) / 1e6
    say("dispatch log: tokens' way back p50 %.3f ms at the interval's "
        "middle, %.3f to %.3f ms over it"
        % (found, found - fitted["slack"] / 2e6,
           found + fitted["slack"] / 2e6))
    return found


def parts(art):
    """{"ns": {part: idle ns}, "by_program": {program after the gap: {part:
    ns}}, "launch_in_call_ns", "inside_ns", "steps", "longest": [(ns, turn,
    program after, part, its ns)], "at_ends": ({part: ns} at the interval's
    lower end, the same at its upper end)} over the idle gaps of the first
    device, first operation to last, with the offset at the interval's
    middle; ``steps`` counts the executions of the programs called from
    ``step.dispatch``.  None without a fit."""
    if "_dispatch_log_parts" in art:
        return art["_dispatch_log_parts"]
    art["_dispatch_log_parts"] = None
    fitted = fit(art)
    if fitted is None:
        return None
    busy = trace_lib.busy_intervals(art["trace"]["devices"][0]["ops"])
    if len(busy) < 2:
        return None
    out = split_at(fitted, busy, fitted["offset"])
    out["at_ends"] = tuple(
        split_at(fitted, busy, fitted["offset"] + side * fitted["slack"])["ns"]
        for side in (-0.5, 0.5))
    art["_dispatch_log_parts"] = out
    report(art, out)
    return out


def split_at(fitted, busy, d):
    """``parts`` with the device's clock ``d`` ahead of the host's."""
    t, r = fitted["tracing"], fitted["rows"]
    # the idle gaps, on the host's clock, and the execution each ends at
    g0 = numpy.array([e for _, e in busy[:-1]]) - d
    g1 = numpy.array([s for s, _ in busy[1:]]) - d
    starts = numpy.array([s for _, s, _ in fitted["execs"]]) - d
    ends = numpy.array([e for _, _, e in fitted["execs"]]) - d
    k = numpy.maximum(numpy.searchsorted(starts, g1, side="right") - 1, 0)
    owned = (g1 >= starts[k]) & (g1 < ends[k])
    inside = owned & (g0 >= starts[k])
    inside_ns = float((g1 - g0)[inside].sum())
    g0, g1, k, owned = g0[~inside], g1[~inside], k[~inside], owned[~inside]
    # launch: behind the call of the dispatch that ends the gap (a gap that
    # ends at an operation of no paired execution has none)
    call = numpy.where(owned, r[k, t.DCOL_CALL], g1)
    back = numpy.where(owned, r[k, t.DCOL_RETURNED], g1)
    called = numpy.clip(call, g0, g1)
    launch = g1 - called
    in_call = numpy.clip(back, called, g1) - called
    # return: where the host waited for a dispatch that had ended; a host
    # thread waits for one thing at a time, so the waits do not overlap
    waited = (r[:, t.DCOL_WAIT] > 0) & (r[:, t.DCOL_FETCHED] > 0)
    w1 = r[waited, t.DCOL_FETCHED].astype(numpy.float64)
    w0 = numpy.clip(ends[waited], r[waited, t.DCOL_WAIT], w1)
    order = numpy.argsort(w1, kind="stable")
    w0, w1 = w0[order], w1[order]
    before = numpy.concatenate([[0.0], numpy.cumsum(w1 - w0)])
    xs = numpy.stack([w0, w1], axis=1).ravel()
    ys = numpy.stack([before[:-1], before[1:]], axis=1).ravel()
    returned = numpy.interp(called, xs, ys) - numpy.interp(g0, xs, ys) \
        if len(xs) else numpy.zeros(len(g0))
    host = (called - g0) - returned
    split = {"return": returned, "launch": launch, "host": host}
    names = [p for p, _, _ in fitted["execs"]]
    after = numpy.array([names[i] if o else "" for i, o in zip(k, owned)])
    length = g1 - g0
    longest = []
    for i in numpy.argsort(-length)[:10].tolist():
        part = max(PARTS, key=lambda p: split[p][i])
        longest.append((float(length[i]),
                        int(r[k[i], t.DCOL_TURN]) if owned[i] else 0,
                        str(after[i]), part, float(split[part][i])))
    return {"ns": {p: float(v.sum()) for p, v in split.items()},
            "by_program": {
                str(name): {p: float(v[after == name].sum())
                            for p, v in split.items()}
                for name in sorted(set(after.tolist()))},
            "launch_in_call_ns": float(in_call.sum()),
            "inside_ns": inside_ns,
            "steps": int((r[:, t.DCOL_PHASE] == t.STEP_DISPATCH).sum()),
            "longest": longest}


def idle_ms(art, part):
    """Idle ms of the device per decode execution in ``part``, with the
    offset at the interval's middle: ``return`` and ``launch`` trade the
    slack between them (only their sum is measured), ``host`` moves only
    where a gap follows an execution nobody waited for."""
    p = parts(art)
    if p is None or not p["steps"]:
        return None
    return p["ns"][part] / p["steps"] / 1e6


def report(art, p):
    """On standard error: the three parts at the interval's middle and at
    its two ends, by the program that followed the gap; the launch split at
    the jit call's return; the gaps inside executions; the longest ten gaps;
    and the totals beside the turn-order reader's where that one holds."""
    steps = max(p["steps"], 1)
    total = sum(p["ns"].values())
    say("dispatch log: idle %.4f s between executions (%s), %.4f s inside "
        "them, %d decode executions"
        % (total / 1e9, ", ".join("%s %.3f ms" % (part, ns / 1e6 / steps)
                                  for part, ns in p["ns"].items()),
           p["inside_ns"] / 1e9, p["steps"]))
    low, high = p["at_ends"]
    say("  measured: return + launch %.3f ms, host %.3f ms; the split of "
        "the first is the midpoint's: over the fit's interval return %.3f "
        "to %.3f, launch %.3f to %.3f, host %.3f to %.3f"
        % ((p["ns"]["return"] + p["ns"]["launch"]) / 1e6 / steps,
           p["ns"]["host"] / 1e6 / steps,
           low["return"] / 1e6 / steps, high["return"] / 1e6 / steps,
           low["launch"] / 1e6 / steps, high["launch"] / 1e6 / steps,
           low["host"] / 1e6 / steps, high["host"] / 1e6 / steps))
    for name, split in p["by_program"].items():
        say("  before %-12s %s (ms per decode execution)"
            % (name or "(no record)",
               "  ".join("%s %.3f" % (part, ns / 1e6 / steps)
                         for part, ns in split.items())))
    say("  launch: %.3f ms inside the jit call, %.3f ms behind its return "
        "(per decode execution)"
        % (p["launch_in_call_ns"] / 1e6 / steps,
           (p["ns"]["launch"] - p["launch_in_call_ns"]) / 1e6 / steps))
    for ns, turn, name, part, own in p["longest"]:
        say("  gap %.3f ms before %s of turn %d: %s %.3f ms"
            % (ns / 1e6, name or "(no record)", turn, part, own / 1e6))
    old = spans.attribution(art)
    if old is not None:
        say("dispatch log: three parts and the gaps inside executions "
            "%.4f s, the turn-order reader's idle %.4f s (difference %.6f s)"
            % ((total + p["inside_ns"]) / 1e9, old["total_ns"] / 1e9,
               (total + p["inside_ns"] - old["total_ns"]) / 1e9))
