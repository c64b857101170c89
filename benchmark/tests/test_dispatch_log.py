"""``lib/dispatch_log.py`` on synthetic timelines with a known offset between
the two clocks.  On today's order (``test_spans.py``'s timeline, its turn rows
extended with the dispatch rows today's engine writes) the new fit equals
``spans.fit`` within slack and each known gap lands in its part.  On three
orders the turn rows cannot express (the fetch one dispatch late, a chunk
called between a step's call and its fetch, a third program) ``spans.fit``
gives None and the new fit recovers the offset and the parts: those timelines
come from the REAL ``LoopRecorder`` driven on a simulated clock beside a
simulated device that runs what it is sent in call order.  A shuffled
sequence, a breach of causality and a program without the ring give None.  Not
part of the repo's tier-1 tests:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_dispatch_log.py -q
"""

import os
import sys
import types

import numpy
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, HERE]

import test_spans as old                             # noqa: E402
from benchmark.lib import dispatch_log, spans        # noqa: E402
from benchmark.lib.files import load_module          # noqa: E402
from benchmark.lib.trace import Module, Op           # noqa: E402
from veles_tpu.serving import tracing                # noqa: E402

OFFSET, T0, US = old.OFFSET, old.T0, old.US
TRACE_READERS = ["dispatches_matched_share.serve", "gap_return_ms.serve",
                 "gap_launch_ms.serve", "gap_host_ms.serve",
                 "token_return_ms.serve"]
READERS = TRACE_READERS + ["jit_call_ms.serve"]
#: float64 holds a device stamp (1.7e18 ns) to 256 ns
ROUNDING = 1024


def read_all(art):
    return {name: load_module("layer_metrics", name).read(art, None)
            for name in READERS}


# ---------------------------------------------- today's order, from test_spans
class Ring(old.Recorder):
    """``test_spans.py``'s stand-in recorder with a dispatch ring."""

    def __init__(self, rows):
        super().__init__()
        self._rows = rows

    def dispatches(self, last=None):
        return self._rows


def rows_of(turns, jit=100 * US):
    """The dispatch rows today's engine writes for these turn rows: a turn's
    chunk (never waited for), then its step, waited for from ``step.fetch``
    and fetched at ``step.emit``."""
    t, s = tracing, tracing.COL_STAMPS
    rows = []
    for turn in turns.tolist():
        for phase, col in ((t.PREFILL_DISPATCH, t.COL_PREFILL_PROGRAM),
                           (t.STEP_DISPATCH, t.COL_STEP_PROGRAM)):
            if not turn[col]:
                continue
            row = [0] * t.DISPATCH_WIDTH
            row[t.DCOL_SEQ] = len(rows) + 1
            row[t.DCOL_TURN] = turn[t.COL_SEQ]
            row[t.DCOL_PROGRAM], row[t.DCOL_PHASE] = turn[col], phase
            row[t.DCOL_CALL] = turn[s + phase]
            row[t.DCOL_RETURNED] = turn[s + phase] + jit
            if phase == t.STEP_DISPATCH:
                row[t.DCOL_LANES] = turn[t.COL_ACTIVE]
                row[t.DCOL_WAIT] = turn[s + t.STEP_FETCH]
                row[t.DCOL_FETCHED] = turn[s + t.STEP_EMIT]
                row[t.DCOL_FETCH_TURN] = turn[t.COL_SEQ]
            rows.append(row)
    return numpy.array(rows, numpy.int64)


def todays(cut=slice(None)):
    turns, modules, ops, expect = old.timeline()
    modules = modules[cut]      # (the list the trace holds: a case edits it)
    art = old.artefacts(turns, modules, ops[cut])
    spans.recorder(art)["recorder"] = Ring(rows_of(turns))
    return art, modules, expect


def test_on_todays_order_the_new_fit_equals_the_old_within_slack():
    art, modules, _ = todays()
    new, was = dispatch_log.fit(art), spans.fit(art)
    assert new is not None and was is not None
    # the same pairs, so the same interval
    assert new["slack"] == was["slack"] <= (60 + 90) * US + ROUNDING
    assert abs(new["offset"] - was["offset"]) <= ROUNDING
    assert abs(new["offset"] - OFFSET) <= new["slack"] / 2 + ROUNDING
    assert len(new["rows"]) == len(new["execs"]) == len(modules) == 48
    assert new["rows"][:, tracing.DCOL_SEQ].tolist() == list(range(1, 49))
    assert read_all(art)["dispatches_matched_share.serve"] == 100.0


def test_a_trace_that_starts_late_is_paired_with_the_right_records():
    art, _, _ = todays(slice(7, -3))
    new = dispatch_log.fit(art)
    assert new is not None
    assert abs(new["offset"] - OFFSET) <= new["slack"] / 2 + ROUNDING
    assert new["rows"][:, tracing.DCOL_SEQ].tolist() == list(range(8, 46))


def test_on_todays_order_each_known_gap_lands_in_its_part():
    """``test_spans.py`` knows the idle of its timeline by phase: the wait
    for the tokens is the return part, the two dispatch phases up to the
    execution's start the launch, every other phase the host's own."""
    art, modules, expect = todays()
    p = dispatch_log.parts(art)
    assert p is not None and p["steps"] == 40 and p["inside_ns"] == 0
    rounding = ROUNDING * len(modules)
    tolerance = len(modules) * dispatch_log.fit(art)["slack"] / 2 \
        + rounding
    want = {"return": expect["step.fetch"],
            "launch": expect["prefill.dispatch"] + expect["step.dispatch"]}
    want["host"] = sum(expect.values()) - sum(want.values())
    # the host's part lies between a fetch and a call, two host stamps: the
    # offset's slack does not move it
    assert abs(p["ns"]["host"] - want["host"]) <= rounding
    for part in ("return", "launch"):
        assert abs(p["ns"][part] - want[part]) <= tolerance, part
    assert abs(sum(p["ns"].values()) - sum(expect.values())) <= rounding
    assert abs(sum(p["ns"].values())
               - spans.attribution(art)["total_ns"]) <= rounding
    # the interval's two ends: the host's part stays, return and launch
    # trade the slack, and the middle lies between
    low, high = p["at_ends"]
    for ends in (low, high):
        assert abs(ends["host"] - want["host"]) <= rounding
        assert abs(sum(ends.values()) - sum(expect.values())) <= rounding
    assert low["return"] < p["ns"]["return"] < high["return"]
    assert high["launch"] < p["ns"]["launch"] < low["launch"]
    assert low["return"] <= want["return"] + rounding
    assert high["launch"] <= want["launch"] + rounding
    # the launch lies behind the jit call's return but for its first 60 us
    assert abs(p["launch_in_call_ns"] - p["ns"]["launch"]) <= tolerance
    assert set(p["by_program"]) == {"chunk_slot", "step_all"}
    for part in dispatch_log.PARTS:
        assert sum(by[part] for by in p["by_program"].values()) \
            == pytest.approx(p["ns"][part])
    # a chunk follows the host's own work; the step behind it queues
    assert p["by_program"]["chunk_slot"]["host"] > 0
    assert len(p["longest"]) == 10
    assert p["longest"][0][0] >= p["longest"][-1][0] > 0
    got = read_all(art)
    assert got["gap_host_ms.serve"] == pytest.approx(
        want["host"] / 40 / 1e6, abs=rounding / 40 / 1e6)
    assert got["gap_return_ms.serve"] + got["gap_launch_ms.serve"] \
        + got["gap_host_ms.serve"] == pytest.approx(
            sum(expect.values()) / 40 / 1e6, abs=rounding / 40 / 1e6)
    # every step's tokens took 90 us from the execution's end
    assert got["token_return_ms.serve"] == pytest.approx(
        0.090, abs=dispatch_log.fit(art)["slack"] / 2e6 + 1e-3)
    assert got["jit_call_ms.serve"] == pytest.approx(0.100)


def test_an_execution_with_no_record_counts_against_the_share():
    """A program the recorder was never told of runs in the gap before a
    step: the fit holds (it pairs the recorder's programs), the share falls
    short, and the part of the gap before that execution has no launch."""
    art, modules, _ = todays()
    whole = dispatch_log.parts(art)["ns"]
    art, modules, _ = todays()
    i = next(k for k in range(1, len(modules))
             if modules[k - 1].name == modules[k].name == "jit_step_all")
    before, after = modules[i - 1], modules[i]
    free = before.start + before.dur
    assert after.start - free > 30 * US
    copy = Module("jit_copy_page", free + 10 * US, 10 * US)
    art["trace"]["devices"][0]["modules"] = modules + [copy]
    art["trace"]["devices"][0]["ops"].append(
        Op("copy f32[8]", copy.start, copy.dur, copy.dur, copy.name))
    got = read_all(art)
    assert got["dispatches_matched_share.serve"] == pytest.approx(
        100 * 48 / 49)
    p = dispatch_log.parts(art)
    assert "" in p["by_program"] and p["by_program"][""]["launch"] == 0
    assert sum(p["ns"].values()) == pytest.approx(
        sum(whole.values()) - 10 * US)


def test_a_shuffled_sequence_gives_none():
    art, modules, _ = todays()
    i = next(k for k, m in enumerate(modules) if m.name == "jit_chunk_slot")
    a, b = modules[i], modules[i + 1]
    modules[i] = Module(b.name, a.start, a.dur)
    modules[i + 1] = Module(a.name, b.start, b.dur)
    got = read_all(art)
    assert {got[name] for name in TRACE_READERS} == {None}
    assert got["jit_call_ms.serve"] == pytest.approx(0.100)


@pytest.mark.parametrize("breach", ["starts before its call",
                                    "ends after its fetch"])
def test_a_breach_of_causality_gives_none(breach):
    art, modules, _ = todays()
    if breach == "starts before its call":
        m = modules[20]
        modules[20] = Module(m.name, m.start - 3_000 * US, m.dur)
    else:
        m = modules[21]
        modules[21] = Module(m.name, m.start, m.dur + 1_090 * US)
    got = read_all(art)
    assert {got[name] for name in TRACE_READERS} == {None}


def test_a_program_without_the_ring_gives_none():
    """The parent of the PR that brought the ring: a recorder with turn rows
    and no ``dispatches``, a ``tracing`` with no ``DCOL_*``; and no recorder
    at all."""
    turns, modules, ops, _ = old.timeline()
    art = old.artefacts(turns, modules, ops)
    assert set(read_all(art).values()) == {None}
    art = todays()[0]
    spans.recorder(art)["tracing"] = types.SimpleNamespace(**{
        k: v for k, v in vars(tracing).items() if not k.startswith("DCOL_")})
    assert set(read_all(art).values()) == {None}
    # what the harness hands over when no engine of the process ran in the
    # window: ``spans.recorder`` looks among ``tracing.recorders()`` itself
    art = {k: v for k, v in todays()[0].items() if not k.startswith("_")}
    assert spans.recorder(art) is None
    assert set(read_all(art).values()) == {None}
    # and the turn-order readers still read what they read
    assert spans.fit(old.artefacts(turns, modules, ops)) is not None


def test_the_turn_order_readers_read_what_they_read():
    """The seven readers on ``lib/spans.py`` are not touched by the ring:
    on today's order they read beside the new ones, the numbers
    ``test_spans.py`` holds them to, and that file's scan for the names that
    begin with ``idle_`` still finds those seven alone."""
    art, modules, expect = todays()
    seven = [n for n in old.IDLE_READERS if n not in READERS]
    assert len(seven) == len(old.IDLE_READERS) == 7
    got = {n: load_module("layer_metrics", n).read(art, None) for n in seven}
    assert None not in got.values()
    assert got["idle_attributed_share.serve"] == pytest.approx(100, abs=0.1)
    assert got["idle_prepare_ms.serve"] == pytest.approx(
        expect["step.prepare"] / 40 / 1e6,
        abs=ROUNDING * len(modules) / 40 / 1e6)


# ------------------------------- orders the turn rows cannot express: simulated
def program(name):
    def fn():
        pass
    fn.__name__ = name
    return fn


CHUNK, STEP, MIXED = (program(n) for n in
                      ("chunk_slot", "step_all", "mixed_all"))


class Sim:
    """The real ``LoopRecorder`` on a simulated clock, and a device that
    starts what it is sent ``launch`` after the call or when the execution
    before ends, whichever is later; its outputs reach the host ``back``
    after the execution's end."""

    def __init__(self, monkeypatch, launch=60 * US, back=90 * US):
        self.now = T0
        self.launch, self.back = launch, back
        self.free = 0
        self.execs = {}                 # handle -> (program, start, end)
        monkeypatch.setattr(tracing, "time", types.SimpleNamespace(
            monotonic_ns=lambda: self.now))
        self.rec = tracing.LoopRecorder("sim", capacity=1 << 10)

    def work(self, us):
        self.now += int(us * US)

    def mark(self, phase, us=0):
        self.rec.mark(phase)
        self.work(us)

    def call(self, fn, phase, run_us, lanes=0, jit_us=100):
        sent = self.rec.dispatch(phase, fn, lanes)
        start = max(self.now + self.launch, self.free)
        self.free = start + int(run_us * US)
        self.execs[sent] = (fn.__name__, start, self.free)
        self.work(jit_us)
        self.rec.returned(sent)
        return sent

    def fetch(self, sent, wait=None, got=None):
        self.rec.waiting(sent, wait)
        self.now = max(self.now, self.execs[sent][2] + self.back)
        self.rec.fetched(sent, got)

    def artefacts(self):
        """``art`` as the harness hands it to the readers; every execution
        is two operations with 7 us between them."""
        self.rec.close()
        mods, ops = [], []
        for name, start, end in self.execs.values():
            m = Module("jit_" + name, float(start + OFFSET),
                       float(end - start))
            half = (end - start) // 2
            mods.append(m)
            ops += [Op("fusion f32[8]", m.start, float(half), float(half),
                       m.name),
                    Op("fusion f32[8]", m.start + half + 7 * US,
                       float(end - start - half - 7 * US),
                       float(end - start - half - 7 * US), m.name)]
        turns = self.rec.turns()
        art = old.artefacts(turns, mods, ops)
        spans.recorder(art)["recorder"] = self.rec
        return art

    def want(self):
        """The three parts by brute force on the TRUE clock: every gap
        between two executions cut at every stamp that may change its kind,
        each piece named by its middle."""
        t = tracing
        rows = self.rec.dispatches()
        execs = [self.execs[s] for s in rows[:, t.DCOL_SEQ].tolist()]
        waits = [(max(w, e[2]), f) for e, w, f in zip(
            execs, rows[:, t.DCOL_WAIT].tolist(),
            rows[:, t.DCOL_FETCHED].tolist()) if w]
        out = dict.fromkeys(dispatch_log.PARTS, 0)
        for (_, _, a), (_, b, _), row in zip(execs, execs[1:], rows[1:]):
            cuts = sorted({a, b, *(x for x in (
                row[t.DCOL_CALL], *(w for pair in waits for w in pair))
                if a < x < b)})
            for lo, hi in zip(cuts, cuts[1:]):
                mid = (lo + hi) / 2
                if mid >= row[t.DCOL_CALL]:
                    out["launch"] += hi - lo
                elif any(w0 <= mid < w1 for w0, w1 in waits):
                    out["return"] += hi - lo
                else:
                    out["host"] += hi - lo
        return out


def todays_order(sim, n):
    """Tick, every third turn a chunk (every ninth a tail), the put, the
    step's call, the stretch under it, the wait, the emit."""
    t = tracing
    for i in range(n):
        sim.rec.turn()
        sim.work(20)
        if i % 3 == 1:
            sim.mark(t.PREFILL_DISPATCH)
            chunk = sim.call(CHUNK, t.PREFILL_DISPATCH, 2_000)
            if i % 9 == 4:
                sim.fetch(chunk)
        sim.mark(t.STEP_PREPARE, 240)
        step = sim.call(STEP, t.STEP_DISPATCH, 5_000 + (i % 7) * 37, lanes=8)
        sim.mark(t.AHEAD_EMIT, 900)
        sim.fetch(step, t.STEP_FETCH, t.STEP_EMIT)
        sim.work(50)


def fetch_one_dispatch_late(sim, n):
    """The next step is called BEFORE the last one's tokens are fetched, so
    a turn's ``step.emit`` stamps the step before; every fifth turn the host
    is slow enough for the device to run dry, and every eighth it waits for
    the step it has just called as well (a boundary: the device finishes and
    the tokens are still on their way)."""
    t = tracing
    flying = None
    for i in range(n):
        sim.rec.turn()
        sim.work(20 if i % 5 else 7_000)
        sim.mark(t.STEP_PREPARE, 240)
        step = sim.call(STEP, t.STEP_DISPATCH, 5_000 + (i % 7) * 37, lanes=8)
        sim.mark(t.AHEAD_EMIT, 300)
        if flying is not None:
            sim.fetch(flying, t.STEP_FETCH, t.STEP_EMIT)
        flying = step
        if i % 8 == 7:
            sim.fetch(step)
            flying = None
        sim.work(50)
    assert flying is None       # (the last turn is a boundary)


def chunk_between_call_and_fetch(sim, n):
    """A turn's chunk goes out under its step: the device runs step, then
    chunk, where the turn's row says chunk, then step."""
    t = tracing
    for i in range(n):
        sim.rec.turn()
        sim.work(20)
        sim.mark(t.STEP_PREPARE, 240)
        step = sim.call(STEP, t.STEP_DISPATCH, 5_000 + (i % 7) * 37, lanes=8)
        sim.mark(t.AHEAD_EMIT, 300)
        if i % 2:
            sim.mark(t.AHEAD_PREPARE, 150)
            chunk = sim.call(CHUNK, t.PREFILL_DISPATCH, 2_000)
            if i % 6 == 3:
                sim.fetch(chunk)
        sim.fetch(step, t.STEP_FETCH, t.STEP_EMIT)
        sim.work(50)


def third_program(sim, n):
    """Every other turn runs a second decode program behind its step: two
    dispatches from ``step.dispatch``, one program column to hold them."""
    t = tracing
    for i in range(n):
        sim.rec.turn()
        sim.work(20)
        sim.mark(t.STEP_PREPARE, 240)
        step = sim.call(STEP, t.STEP_DISPATCH, 5_000 + (i % 7) * 37, lanes=8)
        sim.fetch(step, t.STEP_FETCH, t.STEP_EMIT)
        if i % 2:
            sim.work(180)
            mixed = sim.call(MIXED, t.STEP_DISPATCH, 6_000, lanes=8)
            sim.fetch(mixed, t.STEP_FETCH, t.STEP_EMIT)
        sim.work(50)


ORDERS = {"today": todays_order,
          "fetch_one_dispatch_late": fetch_one_dispatch_late,
          "chunk_between_call_and_fetch": chunk_between_call_and_fetch,
          "third_program": third_program}


@pytest.mark.parametrize("order", sorted(ORDERS))
def test_the_new_fit_recovers_offset_and_parts_whatever_the_order(
        order, monkeypatch):
    sim = Sim(monkeypatch)
    ORDERS[order](sim, 40)
    art = sim.artefacts()
    was = spans.fit(art)
    if order == "today":
        assert was is not None
    else:
        assert was is None
        assert {load_module("layer_metrics", name).read(art, None)
                for name in old.IDLE_READERS
                if name not in READERS} == {None}
    new = dispatch_log.fit(art)
    assert new is not None, order
    execs = len(sim.execs)
    assert len(new["rows"]) == execs
    assert abs(new["offset"] - OFFSET) <= new["slack"] / 2 + ROUNDING
    assert 0 < new["slack"] <= (60 + 90) * US + ROUNDING
    if was is not None:
        assert abs(new["offset"] - was["offset"]) <= was["slack"]
    p = dispatch_log.parts(art)
    want = sim.want()
    rounding = ROUNDING * execs
    tolerance = execs * new["slack"] / 2 + rounding
    for part in dispatch_log.PARTS:
        assert abs(p["ns"][part] - want[part]) <= tolerance, (part, p, want)
    assert abs(sum(p["ns"].values()) - sum(want.values())) <= rounding
    assert abs(p["inside_ns"] - execs * 7 * US) <= rounding
    assert p["steps"] == sum(
        1 for name, _, _ in sim.execs.values() if name != "chunk_slot")
    assert want["return"] > 0 and want["launch"] > 0 and want["host"] > 0
    got = read_all(art)
    assert got["dispatches_matched_share.serve"] == 100.0
    assert set(got) == set(READERS) and None not in got.values()
    assert got["gap_return_ms.serve"] == pytest.approx(
        p["ns"]["return"] / p["steps"] / 1e6)
    assert got["jit_call_ms.serve"] == pytest.approx(0.100)
    half = new["slack"] / 2e6 + 1e-3
    if order == "fetch_one_dispatch_late":
        rows = new["rows"]
        late = rows[:, tracing.DCOL_FETCH_TURN] > rows[:, tracing.DCOL_TURN]
        assert int(late.sum()) == 40 - 5
        # a step's tokens lie on the device while the host makes the next
        # call: the latency is there where little idle is
        assert got["token_return_ms.serve"] >= 0.090 - half
        assert got["gap_return_ms.serve"] < 0.090
    else:
        assert got["token_return_ms.serve"] == pytest.approx(0.090, abs=half)
    if order == "third_program":
        assert set(p["by_program"]) == {"step_all", "mixed_all"}
    # the longest gaps name the turn that made the call behind them
    assert all(1 <= turn <= sim.rec.head for _, turn, _, _, _
               in p["longest"])
