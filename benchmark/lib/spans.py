"""The program's own record of its engine loop, read by the benchmark: the
loop recorder of ``veles_tpu/serving/tracing.py`` (one record per turn of
``LMEngine._serve_loop`` whose phases partition the turn, one per request
with the stamp of every emitted token, one per HTTP POST; all on
``time.monotonic_ns()``, the clock of ``art["t_open"]`` and
``art["trace_host_window"]``), cut to a window, and put on the clock of the
device trace so that each idle gap of the device is split over the host
phases that overlap it.

The benchmark drives ``serve_lm`` inside its own process and the readers run
after ``driver.release()``; the recorder outlives ``api.stop()``, so it is
read here with no edit to the harness.  A program without the recorder (the
parent of the PR that brought it) gives None everywhere, and the metric is
left out.

**One clock with the device trace** (``fit``).  Nothing is assumed about the
epoch of the xplane's timestamps.  The device's program executions (first
device, by name, in order) are matched to the recorder's dispatches (by
program, in order), and the offset ``d = T_device - T_monotonic`` is bounded
from causality: no execution starts before the host began the dispatch that
launched it (``d <= start_k - call_k``), and no decode execution ends after
the host had its tokens (``d >= end_k - fetch_end_k``).  What is left is as
wide as the launch latency plus the fetch latency at the tightest pair; its
middle is taken and its width printed on standard error as ``clock fit
slack``.  An empty interval, or sequences that do not match, give None."""

from __future__ import annotations

import sys

import numpy

from benchmark.lib import stats, trace as trace_lib

def _tracing():
    try:
        from veles_tpu.serving import tracing
    except ImportError:
        return None
    return tracing if hasattr(tracing, "recorders") else None


def say(text):
    print("spans: " + text, file=sys.stderr)


def window_ns(art):
    return (int(art["t_open"] * 1e9),
            int((art["t_open"] + art["window_s"]) * 1e9))


def recorder(art):
    """The loop recorder that served the window: of those the process keeps,
    the one with most turns inside it.  None when the program has no
    recorder, or none of them ran in the window."""
    if "_spans_recorder" in art:
        return art["_spans_recorder"]
    tracing = _tracing()
    best = None
    if tracing is not None:
        lo, hi = window_ns(art)
        for rec in tracing.recorders():
            turns = rec.turns()
            if not len(turns):
                continue
            inside = int(((turns[:, tracing.COL_END] > lo)
                          & (turns[:, tracing.COL_STAMPS] < hi)).sum())
            if inside and (best is None or inside > best[0]):
                best = (inside, rec, turns)
    art["_spans_recorder"] = best and {"recorder": best[1],
                                       "turns": best[2],
                                       "tracing": tracing}
    return art["_spans_recorder"]


# ------------------------------------------------------------ recorder alone
def decode_turns(art):
    """The turns of the window that dispatched a decode program (an int64
    array, ``tracing.COL_*`` columns), or None."""
    found = recorder(art)
    if found is None:
        return None
    t, turns = found["tracing"], found["turns"]
    lo, hi = window_ns(art)
    keep = (turns[:, t.COL_STEP_PROGRAM] > 0) \
        & (turns[:, t.COL_STAMPS] >= lo) & (turns[:, t.COL_END] <= hi)
    return turns[keep]


def host_turn_ms(art):
    """Median over the window's decode turns of the turn's length less its
    ``step.fetch``: the host's own work per token step."""
    turns = decode_turns(art)
    if turns is None or not len(turns):
        return None
    t = recorder(art)["tracing"]
    s = t.COL_STAMPS
    own = (turns[:, t.COL_END] - turns[:, s]) \
        - (turns[:, s + t.STEP_EMIT] - turns[:, s + t.STEP_FETCH])
    return float(numpy.median(own)) / 1e6


def phase_lengths_ms(art):
    """{phase: sorted lengths in ms over the window's decode turns}: what
    PERF.md's table of medians and 99th percentiles is read from."""
    turns = decode_turns(art)
    if turns is None or not len(turns):
        return None
    t = recorder(art)["tracing"]
    s = t.COL_STAMPS
    return {phase: numpy.sort(turns[:, s + i + 1] - turns[:, s + i]) / 1e6
            for i, phase in enumerate(t.PHASES)}


def finished_requests(art):
    """Request records with outcome ``ok`` whose ``done`` lies in the
    window, or None."""
    found = recorder(art)
    if found is None:
        return None
    lo, hi = window_ns(art)
    return [r for r in found["recorder"].requests()
            if r.outcome == "ok" and lo <= r.done <= hi]


def queue_wait_share(art):
    """Sum of ``admit - enqueue`` over sum of ``done - enqueue`` (%), the
    requests finished in the window."""
    reqs = finished_requests(art)
    if not reqs:
        return None
    total = sum(r.done - r.enqueue for r in reqs)
    return 100.0 * sum(r.admit - r.enqueue for r in reqs) / total


def ttft_percentile_ms(art, q):
    reqs = finished_requests(art)
    if not reqs:
        return None
    return stats.percentile(
        [(r.first_token - r.enqueue) / 1e6 for r in reqs], q)


def token_gaps_ms(art):
    """Gaps (ms) between one request's successive token stamps, the later
    token stamped in the window; every request the recorder kept."""
    found = recorder(art)
    if found is None:
        return None
    lo, hi = window_ns(art)
    gaps = []
    for r in found["recorder"].requests():
        stamps = numpy.frombuffer(r.token_ns, numpy.int64) \
            if len(r.token_ns) else numpy.zeros(0, numpy.int64)
        if len(stamps) < 2:
            continue
        later = stamps[1:]
        keep = (later >= lo) & (later <= hi)
        gaps.append((later - stamps[:-1])[keep])
    if not gaps:
        return None
    return numpy.concatenate(gaps) / 1e6


def itl_percentile_ms(art, q):
    gaps = token_gaps_ms(art)
    if gaps is None or not len(gaps):
        return None
    return float(stats.percentile(gaps.tolist(), q))


def lane_ms_per_token(art):
    """(mean ms of lane time per token stamped in the window, mean lanes
    busy over the window's turns).  A lane is held from ``admit`` to the
    request's last token; the part of that inside the window, summed over
    requests and divided by the tokens stamped in the window, is by Little's
    law lanes busy over tokens per second, which the mean gap between tokens
    alone (the prompt's chunks left out) is not."""
    found = recorder(art)
    if found is None:
        return None
    t, turns = found["tracing"], found["turns"]
    lo, hi = window_ns(art)
    held = count = 0
    for r in found["recorder"].requests():
        if not len(r.token_ns):
            continue
        stamps = numpy.frombuffer(r.token_ns, numpy.int64)
        held += max(0, min(int(stamps[-1]), hi) - max(r.admit, lo))
        count += int(((stamps >= lo) & (stamps <= hi)).sum())
    inside = turns[(turns[:, t.COL_STAMPS] >= lo)
                   & (turns[:, t.COL_END] <= hi)]
    if not count or not len(inside):
        return None
    # lanes busy, weighted by each turn's length
    length = (inside[:, t.COL_END] - inside[:, t.COL_STAMPS]) \
        .astype(numpy.float64)
    busy = float((inside[:, t.COL_BUSY] * length).sum() / length.sum())
    return held / count / 1e6, busy


def tokens_stamped(art):
    """Token stamps inside the window, over every request kept."""
    found = recorder(art)
    if found is None:
        return None
    lo, hi = window_ns(art)
    return sum(sum(1 for t in r.token_ns if lo <= t <= hi)
               for r in found["recorder"].requests())


def http_overhead_percentile_ms(art, q):
    """``(reply - recv) - (result - submit)`` of the replies written in the
    window: what the HTTP layer adds around the handler."""
    tracing = _tracing()
    if tracing is None:
        return None
    lo, hi = window_ns(art)
    own = [((h.reply - h.recv) - (h.result - h.submit)) / 1e6
           for h in tracing.http_records()
           if h.status == 200 and lo <= h.reply <= hi]
    return stats.percentile(own, q)


# ------------------------------------------------------- the clock, the gaps
def dispatches(found, lo, hi):
    """[(program, call stamp, fetch-end stamp or 0)] of the recorder's
    dispatches whose call lies in [lo, hi] (monotonic ns), in order: a turn's
    prefill chunk, then its decode step."""
    t, turns, rec = found["tracing"], found["turns"], found["recorder"]
    s = t.COL_STAMPS
    out = []
    for row in turns[(turns[:, t.COL_END] >= lo)
                     & (turns[:, s] <= hi)].tolist():
        if row[t.COL_PREFILL_PROGRAM]:
            out.append((rec.programs[row[t.COL_PREFILL_PROGRAM]],
                        row[s + t.PREFILL_DISPATCH], 0))
        if row[t.COL_STEP_PROGRAM]:
            out.append((rec.programs[row[t.COL_STEP_PROGRAM]],
                        row[s + t.STEP_DISPATCH], row[s + t.STEP_EMIT]))
    return [d for d in out if lo <= d[1] <= hi]


def executions(trace, programs):
    """[(program, start, end)] of the first device's executions of the
    recorder's programs, by start; ``jit_`` is the trace's prefix."""
    names = {"jit_" + p: p for p in programs if p}
    names.update({p: p for p in programs if p})
    mods = sorted((m for m in trace["devices"][0]["modules"]
                   if m.name in names), key=lambda m: m.start)
    return [(names[m.name], m.start, m.start + m.dur) for m in mods]


def bounds(execs, calls):
    """(lo, hi) of ``d = T_device - T_monotonic`` from causality over the
    matched pairs, or None where a name differs."""
    lo, hi = -float("inf"), float("inf")
    for (name, start, end), (program, call, fetched) in zip(execs, calls):
        if name != program:
            return None
        hi = min(hi, start - call)
        if fetched:
            lo = max(lo, end - fetched)
    return lo, hi


def fit(art, margin_s=2.0):
    """{"offset", "slack", "executions", "by_program"} (ns, counts) with
    ``offset`` the middle of the interval causality leaves for ``T_device -
    T_monotonic``; None when the program has no recorder, the run no trace,
    the sequences do not match, or the interval is empty.  The trace holds
    the executions that ran while it was on; which of the recorder's
    dispatches is its first is found by trying each start near the traced
    host window and keeping the one alignment that causality allows."""
    if "_spans_fit" in art:
        return art["_spans_fit"]
    art["_spans_fit"] = None
    found = recorder(art)
    trace = art.get("trace")
    if found is None or not trace or not trace["devices"] \
            or not art.get("trace_host_window"):
        return None
    execs = executions(trace, found["recorder"].programs)
    begin, end = (int(x * 1e9) for x in art["trace_host_window"])
    margin = int(margin_s * 1e9)
    calls = dispatches(found, begin - margin, end + margin)
    if len(execs) < 2 or len(calls) < len(execs):
        say("clock fit: %d executions in the trace, %d dispatches recorded "
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
        say("clock fit: %d alignments of %d executions on %d dispatches "
            "satisfy causality (want exactly 1): no mapping"
            % (len(feasible), len(execs), len(calls)))
        return None
    k, (lo, hi) = feasible[0]
    offset = (lo + hi) / 2.0
    # the host's stop_trace takes seconds, so the host's traced window is far
    # longer than the trace: dispatches are counted over what the trace
    # holds, the first execution's start to the last's end on the fitted
    # clock (the first's own call lies before it: one fewer than executions)
    first = execs[0][1] - offset
    last = execs[-1][2] - offset
    art["_spans_fit"] = {
        "offset": offset, "slack": hi - lo, "executions": len(execs),
        "by_program": {
            p: (sum(1 for e in execs if e[0] == p),
                sum(1 for c in calls if c[0] == p and first <= c[1] <= last))
            for p in sorted({e[0] for e in execs})}}
    say("clock fit slack %.1f us over %d executions (%s; executions in the "
        "trace, dispatches recorded from the first's start to the "
        "last's end)"
        % ((hi - lo) / 1e3, len(execs),
           ", ".join("%s %d/%d" % (p, a, b) for p, (a, b)
                     in art["_spans_fit"]["by_program"].items())))
    return art["_spans_fit"]


def attribution(art):
    """{"idle_ns": {phase: idle ns of the first device inside it},
    "total_ns", "unattributed_ns", "steps"}: each idle gap of the first
    device (between the end of one operation's busy interval and the start
    of the next, first operation to last) split over the host phases that
    overlap it; ``steps`` counts the decode program's executions.  None
    without a clock fit."""
    if "_spans_attribution" in art:
        return art["_spans_attribution"]
    art["_spans_attribution"] = None
    fitted = fit(art)
    if fitted is None:
        return None
    found = recorder(art)
    t, turns, rec = found["tracing"], found["turns"], found["recorder"]
    busy = trace_lib.busy_intervals(art["trace"]["devices"][0]["ops"])
    if len(busy) < 2:
        return None
    # idle gaps on the monotonic clock; F(x) = idle ns before x
    g0 = numpy.array([e for _, e in busy[:-1]]) - fitted["offset"]
    g1 = numpy.array([s for s, _ in busy[1:]]) - fitted["offset"]
    before = numpy.concatenate([[0.0], numpy.cumsum(g1 - g0)])
    xs = numpy.stack([g0, g1], axis=1).ravel()
    ys = numpy.stack([before[:-1], before[1:]], axis=1).ravel()
    total = float(before[-1])
    # every boundary of every turn, in order: turns leave no hole
    s = t.COL_STAMPS
    near = turns[(turns[:, t.COL_END] >= g0[0]) & (turns[:, s] <= g1[-1])]
    edges = near[:, s:t.COL_END + 1].astype(numpy.float64)
    idle = numpy.interp(edges[:, 1:], xs, ys) \
        - numpy.interp(edges[:, :-1], xs, ys)
    per_phase = idle.sum(axis=0)
    step_names = {rec.programs[i] for i in
                  set(near[:, t.COL_STEP_PROGRAM].tolist()) if i}
    steps = sum(1 for e in executions(art["trace"], step_names))
    out = {"idle_ns": dict(zip(t.PHASES, per_phase.tolist())),
           "total_ns": total,
           "unattributed_ns": total - float(per_phase.sum()),
           "steps": steps}
    art["_spans_attribution"] = out
    report(art, out)
    return out


def idle_ms(art, phases):
    """Idle ms of the device per decode execution inside ``phases``."""
    a = attribution(art)
    if a is None or not a["steps"]:
        return None
    return sum(a["idle_ns"][p] for p in phases) / a["steps"] / 1e6


def idle_attributed_share(art):
    a = attribution(art)
    if a is None or not a["total_ns"]:
        return None
    return 100.0 * (1.0 - a["unattributed_ns"] / a["total_ns"])


def report(art, a):
    """The cross-checks, on standard error: where the idle time went by
    phase; token stamps against the ``tokens_out`` difference; the mean
    token gap against lanes busy over tokens per second."""
    say("idle %.4f s between the first and the last operation, %d decode "
        "executions; unattributed %.6f s"
        % (a["total_ns"] / 1e9, a["steps"], a["unattributed_ns"] / 1e9))
    for phase, ns in a["idle_ns"].items():
        if ns:
            say("  idle in %-16s %.4f s  %.3f ms per decode execution"
                % (phase, ns / 1e9, ns / 1e6 / max(a["steps"], 1)))
    lengths = phase_lengths_ms(art) or {}
    for phase, ms in lengths.items():
        if ms[-1] > 0:
            say("  %-16s length p50 %.3f ms  p99 %.3f ms  (%d decode turns)"
                % (phase, ms[len(ms) // 2],
                   ms[min(len(ms) - 1, int(0.99 * len(ms)))], len(ms)))
    stamped = tokens_stamped(art)
    counted = art["counters"].get("tokens_out")
    gaps = token_gaps_ms(art)
    say("tokens stamped in the window %s, tokens_out difference %s"
        % (stamped, counted))
    lane = lane_ms_per_token(art)
    if gaps is not None and len(gaps) and lane is not None and counted:
        rate = counted / art["window_s"]
        say("mean gap between a request's tokens %.3f ms; mean lane time "
            "per token (admit to last token, inside the window) %.3f ms; "
            "lanes busy %.3f over %.4f tokens/s = %.3f ms"
            % (float(gaps.mean()), lane[0], lane[1], rate,
               1e3 * lane[1] / rate))
