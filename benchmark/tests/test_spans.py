"""``lib/spans.py`` on a synthetic trace and synthetic turn records with a
known offset between the two clocks: the fit recovers the offset within its
slack, each known gap lands in its phase, and a shuffled sequence or a breach
of causality makes every ``idle_*`` reader return None.  Not part of the
repo's tier-1 tests:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_spans.py -q
"""

import array
import os
import sys

import numpy
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import spans                      # noqa: E402
from benchmark.lib.files import load_json, load_module  # noqa: E402
from benchmark.lib.trace import Module, Op           # noqa: E402
from veles_tpu.serving import tracing                # noqa: E402

#: the device clock runs this far ahead of the monotonic one (about what a
#: Unix-epoch trace against an uptime clock gives); nothing may assume it
OFFSET = 1_727_000_000_123_456_789
T0 = 5_000_000_000_000          # monotonic ns at the first turn
US = 1_000
IDLE_READERS = [m["name"] for m in load_json(
    os.path.join(ROOT, "BENCHMARK.json"))["per_layer"]
    if m["name"].startswith("idle_")]


class Recorder:
    programs = ["", "chunk_slot", "step_all"]

    def __init__(self, requests=()):
        self._requests = list(requests)

    def requests(self):
        return self._requests


def timeline(turns=40, launch=60 * US, fetch=90 * US):
    """(turn records, device modules, ops, expected idle ns by phase).  Every
    fifth turn runs a prompt chunk before its decode step.  The device starts
    an execution ``launch`` after the host's call (or when the one before it
    ends), and the host has the tokens ``fetch`` after the execution ends."""
    t = tracing
    rows, mods, expect = [], [], dict.fromkeys(t.PHASES, 0)
    now, device_free = T0, None

    def idle(phase, a, b):
        # the device is idle from device_free to the next start
        if device_free is not None and b > max(a, device_free):
            expect[phase] += b - max(a, device_free)

    for n in range(turns):
        row = [0] * t.TURN_WIDTH
        row[t.COL_SEQ] = n + 1
        stamps = {t.TICK: now}
        stamps[t.ADMIT] = now + 10 * US
        cursor = now + 40 * US
        busy_from = []          # (phase, start, end) host intervals so far
        busy_from += [("loop.tick", now, stamps[t.ADMIT]),
                      ("loop.admit", stamps[t.ADMIT], cursor)]
        if n % 5 == 2:
            stamps[t.PREFILL_PREPARE] = cursor
            stamps[t.PREFILL_DISPATCH] = cursor + 150 * US
            start = stamps[t.PREFILL_DISPATCH] + launch
            end = start + 2_000 * US
            busy_from += [("prefill.prepare", cursor,
                           stamps[t.PREFILL_DISPATCH]),
                          ("prefill.dispatch", stamps[t.PREFILL_DISPATCH],
                           start)]
            for phase, a, b in busy_from:
                idle(phase, a, b)
            busy_from = []
            mods.append(("jit_chunk_slot", start, end))
            device_free = end
            row[t.COL_PREFILL_PROGRAM] = 1
            # the jit call returns at once; nothing waits for the chunk
            cursor = stamps[t.PREFILL_DISPATCH] + 300 * US
            busy_from.append(("prefill.dispatch", start, cursor))
        stamps[t.STEP_PREPARE] = cursor
        stamps[t.STEP_DISPATCH] = cursor + 400 * US
        busy_from += [("step.prepare", cursor, stamps[t.STEP_DISPATCH])]
        start = max(stamps[t.STEP_DISPATCH] + launch, device_free or 0)
        busy_from.append(("step.dispatch", stamps[t.STEP_DISPATCH], start))
        for phase, a, b in busy_from:
            idle(phase, a, min(b, start))
        end = start + 5_000 * US + (n % 7) * 37 * US
        mods.append(("jit_step_all", start, end))
        device_free = end
        stamps[t.STEP_FETCH] = stamps[t.STEP_DISPATCH] + 500 * US
        stamps[t.STEP_EMIT] = end + fetch
        expect["step.fetch"] += fetch
        finish = stamps[t.STEP_EMIT] + 200 * US
        expect["step.emit"] += 200 * US
        device_free = finish    # idle up to here is already counted
        for i in range(len(t.PHASES)):
            row[t.COL_STAMPS + i] = stamps.get(i, 0)
        row[t.COL_END] = finish
        for i in range(t.COL_END - 1, t.COL_STAMPS, -1):
            if not row[i]:
                row[i] = row[i + 1]
        row[t.COL_STEP_PROGRAM] = 2
        row[t.COL_BUSY] = row[t.COL_ACTIVE] = 8
        row[t.COL_TOKENS] = 8
        rows.append(row)
        now = finish
    # the last turn's fetch and emit have no next operation after them
    expect["step.fetch"] -= fetch
    expect["step.emit"] -= 200 * US
    modules = [Module(name, float(a + OFFSET), float(b - a))
               for name, a, b in mods]
    ops = [Op("fusion f32[8]", m.start, m.dur, m.dur, m.name)
           for m in modules]
    return numpy.array(rows, numpy.int64), modules, ops, expect


def artefacts(turns, modules, ops, requests=()):
    first, last = turns[0, tracing.COL_STAMPS], turns[-1, tracing.COL_END]
    art = {"t_open": (first - 10**9) / 1e9,
           "window_s": (last - first + 2 * 10**9) / 1e9,
           "trace_host_window": ((first - 10**6) / 1e9, (last + 10**6) / 1e9),
           "trace": {"devices": [{"ops": ops, "modules": modules}]},
           "counters": {"tokens_out": 8 * len(turns)}}
    art["_spans_recorder"] = {"recorder": Recorder(requests),
                              "turns": turns, "tracing": tracing}
    return art


def read_all(art):
    return {name: load_module("layer_metrics", name).read(art, None)
            for name in IDLE_READERS}


def test_the_fit_recovers_the_offset_within_its_slack():
    turns, modules, ops, _ = timeline()
    fitted = spans.fit(artefacts(turns, modules, ops))
    assert fitted is not None
    assert 0 < fitted["slack"] <= (60 + 90) * US + 1024
    assert abs(fitted["offset"] - OFFSET) <= fitted["slack"] / 2 + 1024
    # the first execution's own call lies before its start
    assert fitted["by_program"] == {"chunk_slot": (8, 8),
                                    "step_all": (40, 39)}


def test_a_trace_that_starts_late_is_aligned_to_the_right_dispatch():
    """The trace holds fewer executions than the recorder has dispatches
    around its window: the alignment that causality allows is the true one."""
    turns, modules, ops, _ = timeline()
    fitted = spans.fit(artefacts(turns, modules[7:-3], ops[7:-3]))
    assert fitted is not None
    assert abs(fitted["offset"] - OFFSET) <= fitted["slack"] / 2 + 1024


def test_each_known_gap_lands_in_its_phase():
    turns, modules, ops, expect = timeline()
    art = artefacts(turns, modules, ops)
    a = spans.attribution(art)
    assert a is not None and a["steps"] == 40
    # the offset is known to within half the slack, so a gap's two edges may
    # move by that much: between the phase a gap begins in (step.fetch: the
    # device has finished) and the one it ends in (a dispatch: the launch).
    # Every other phase holds no edge and is exact, but for float rounding
    rounding = 1024 * len(modules)
    tolerance = len(modules) * art["_spans_fit"]["slack"] / 2 + rounding
    for phase in tracing.PHASES:
        edge = phase in ("prefill.dispatch", "step.dispatch", "step.fetch")
        assert abs(a["idle_ns"][phase] - expect[phase]) \
            <= (tolerance if edge else rounding), phase
    assert a["idle_ns"]["loop.wait"] == 0
    assert abs(a["unattributed_ns"]) <= 1024 * len(modules)
    assert abs(a["total_ns"] - sum(expect.values())) <= tolerance
    got = read_all(art)
    assert set(got) == set(IDLE_READERS) and len(got) == 7
    assert got["idle_attributed_share.serve"] == pytest.approx(100, abs=0.1)
    assert got["idle_prepare_ms.serve"] == pytest.approx(
        expect["step.prepare"] / 40 / 1e6, abs=rounding / 40 / 1e6)


def test_a_shuffled_sequence_gives_none():
    turns, modules, ops, _ = timeline()
    # a chunk's execution and the step's after it change places
    i = next(k for k, m in enumerate(modules) if m.name == "jit_chunk_slot")
    a, b = modules[i], modules[i + 1]
    modules[i] = Module(b.name, a.start, a.dur)
    modules[i + 1] = Module(a.name, b.start, b.dur)
    assert set(read_all(artefacts(turns, modules, ops)).values()) == {None}


def test_a_breach_of_causality_gives_none():
    turns, modules, ops, _ = timeline()
    m = modules[20]             # starts 3 ms before the host dispatched it
    modules[20] = Module(m.name, m.start - 3_000 * US, m.dur)
    assert set(read_all(artefacts(turns, modules, ops)).values()) == {None}
    turns, modules, ops, _ = timeline()
    m = modules[21]             # ends 1 ms after the host had its tokens
    modules[21] = Module(m.name, m.start, m.dur + 1_000 * US + 90 * US)
    assert set(read_all(artefacts(turns, modules, ops)).values()) == {None}


def test_a_program_without_the_recorder_gives_none():
    turns, modules, ops, _ = timeline()
    art = artefacts(turns, modules, ops)
    art["_spans_recorder"] = None
    assert set(read_all(art).values()) == {None}
    for name in ("host_turn_ms.serve", "queue_wait_share.chat",
                 "ttft_p50_ms.chat", "itl_p50_ms.chat", "itl_p99_ms.chat"):
        assert load_module("layer_metrics", name).read(art, None) is None


def test_the_request_readers_on_known_records():
    turns, modules, ops, _ = timeline()
    ms = 1_000_000

    def request(enqueue, wait, first, gap, n):
        stamps = array.array("q", [enqueue + wait + first + i * gap
                                   for i in range(n)])
        return tracing.RequestRecord(
            enqueue, enqueue + wait, stamps[0], stamps[-1] + ms, 16, n, n,
            0, "ok", stamps)

    reqs = [request(T0, 2 * ms, 30 * ms, 5 * ms, 6),
            request(T0 + ms, 0, 50 * ms, 7 * ms, 4)]
    art = artefacts(turns, modules, ops, reqs)
    read = lambda name: load_module("layer_metrics", name).read(art, None)
    done = [(2 + 30 + 25 + 1), (50 + 21 + 1)]
    assert read("queue_wait_share.chat") == pytest.approx(
        100.0 * 2 / sum(done))
    assert read("ttft_p50_ms.chat") == pytest.approx(50.0)
    assert read("itl_p50_ms.chat") == pytest.approx(5.0)
    assert read("itl_p99_ms.chat") == pytest.approx(7.0)
    per_turn = turns[:, tracing.COL_END] - turns[:, tracing.COL_STAMPS] \
        - (turns[:, tracing.COL_STAMPS + tracing.STEP_EMIT]
           - turns[:, tracing.COL_STAMPS + tracing.STEP_FETCH])
    assert read("host_turn_ms.serve") == pytest.approx(
        float(numpy.median(per_turn)) / 1e6)
