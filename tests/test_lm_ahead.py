"""ISSUE 37: the part of a turn that needs no token runs while the device
runs the step.  ``LMEngine._serve_loop``'s paged plain driver delivers the
tokens of the step before, sheds and admits, and prepares the next turn's
chunk and step BETWEEN a decode step's jit call and the wait for its tokens
(``_under_step``, the ``ahead.*`` phases).  Held here: the served tokens are
the references' own through the reordered loop, for the four kinds of
engine the cells run; the turn's row keeps the shape the benchmark's readers
rely on; an argument put ahead never shares memory with what the loop
changes in place; a fault, a cancel or a weight swap that lands between
preparation and dispatch drops what was prepared and fails the right lanes
only; and the two counters count what they say.

ISSUE 39: two dispatches in flight.  The same driver keeps the lanes' last
tokens on the device and waits for the tokens of step N only after it has
called turn N+1's chunk and step.  Held here, as more cases of the same
tests: the tokens through the pipelined loop, a tail chunk whose first token
is its request's last, what lands with two dispatches in flight (a cancel, a
swap either way, a checkpoint, faults), a fetch that raises with a younger
dispatch in flight, and the two counters of the mechanism.

No case asserts a duration: only order, counts, identities and tokens."""

import threading

import numpy
import pytest

import jax
import jax.numpy as jnp

from lm_cases import (ROUND, assert_greedy, check_tokens, counters,
                      follows_a_step, make_engine, pipeline_balances, serve,
                      stamps_of, tokens, vocab_of)
from veles_tpu import model_config
from veles_tpu.serving import tracing

# ------------------------------------------------------- (a) the tokens
@pytest.mark.parametrize("kind", ["pre_ln", "window", "latent", "linear"])
def test_the_reordered_loop_serves_the_references_tokens(kind):
    """Seven requests on three lanes, so prompts end in the turns in which
    others start, lanes are freed by count under their last step and taken
    again under the same step: every token is the reference's, every page
    and slot comes home, and most decode steps were prepared ahead."""
    engine = make_engine(kind, name="ahead_" + kind)
    prompts, outs = serve(engine)
    for p, o, (_, n_new) in zip(prompts, outs, ROUND):
        check_tokens(kind, engine, p, o, n_new)
    assert engine.verify_pool_invariants()["used_pages"] == 0
    assert engine._ahead is None and not engine._undelivered
    c = pipeline_balances(engine)
    assert c["tokens_out"] == sum(n for _, n in ROUND)
    assert c.get("kv_storage_rebuilds", 0) == 0
    assert c["turns_prepared_ahead"] > c["decode_dispatches"] // 2
    assert c["dispatches_sent_ahead"] > c["decode_dispatches"] // 2
    # every request's first token reached the host once, late or not
    assert engine.metrics.snapshot()["ttft"]["count"] == len(ROUND)
    # the one-token answer ends at its tail chunk, after the step that took
    # its lane for a decoding one was prepared: that one preparation goes
    assert c.get("ahead_discarded", 0) <= 1


@pytest.mark.parametrize("kind", ["pre_ln", "window", "latent", "linear"])
def test_every_dispatch_has_one_record_under_prepared_turns(kind):
    """ISSUE 38: through stretches of turns prepared under the step before
    (most of them: ``turns_prepared_ahead``), every call of a jitted program
    still has exactly one dispatch record, in call order.  ISSUE 39: each
    step was fetched in the turn that called it (a drain) or in the next
    (its follower went out first), inside that turn's ``step.fetch``; a tail
    chunk with the step behind it; and the step's record lies over the
    ``ahead.*`` phases of its own turn (called before them)."""
    t = tracing
    round_ = [(n, max(n_new, 2)) for n, n_new in ROUND]
    engine = make_engine(kind, name="rows_" + kind)
    serve(engine, round_)
    rec, c = engine.recorder, pipeline_balances(engine)
    assert c["turns_prepared_ahead"] > c["decode_dispatches"] // 2
    rows, turns = rec.dispatches(), rec.turns()
    assert len(rows) == c["decode_dispatches"] + c["prefill_dispatches"]
    assert rows[:, t.DCOL_SEQ].tolist() == list(range(1, len(rows) + 1))
    assert (numpy.diff(rows[:, t.DCOL_CALL]) > 0).all()
    fetched = rows[:, t.DCOL_FETCHED] > 0
    late = rows[:, t.DCOL_FETCH_TURN] - rows[:, t.DCOL_TURN]
    assert set(late[fetched].tolist()) == {0, 1}
    step = rows[:, t.DCOL_PHASE] == t.STEP_DISPATCH
    assert fetched[step].all()
    assert int((late[step] == 1).sum()) == c["dispatches_sent_ahead"]
    assert int((~step & fetched).sum()) == len(round_)      # the tails
    # fetched in call order, the order the device ran them
    assert (numpy.diff(rows[fetched, t.DCOL_FETCHED]) > 0).all()
    # a tail chunk is fetched with its own turn's step, or before it where
    # the turn was not prepared (the old order begins with a drain)
    for i in numpy.flatnonzero(~step & fetched).tolist():
        assert step[i + 1] and rows[i + 1, t.DCOL_TURN] == rows[i, t.DCOL_TURN]
        assert rows[i, t.DCOL_FETCH_TURN] <= rows[i + 1, t.DCOL_FETCH_TURN]
    # one chunk and one step a turn at most, the chunk first
    for of in (step, ~step):
        assert len(set(rows[of, t.DCOL_TURN].tolist())) == int(of.sum())
    assert (numpy.diff(rows[:, t.DCOL_TURN]) >= 0).all()
    s = stamps_of(turns[rows[step, t.DCOL_TURN] - 1])
    assert (rows[step, t.DCOL_CALL] == s[:, t.STEP_DISPATCH]).all()
    assert (rows[step, t.DCOL_RETURNED] <= s[:, t.AHEAD_EMIT]).all()
    assert (s[:, t.STEP_FETCH] > s[:, t.AHEAD_EMIT]).all()
    # a step's wait lies inside the ``step.fetch`` of the turn that made it
    # (a tail's too, but in a turn of the old order, which drains first)
    f = stamps_of(turns[rows[fetched, t.DCOL_FETCH_TURN] - 1])
    assert (f[step[fetched], t.STEP_FETCH]
            <= rows[fetched & step, t.DCOL_WAIT]).all()
    assert (rows[fetched, t.DCOL_WAIT] <= rows[fetched, t.DCOL_FETCHED]).all()
    assert (rows[fetched, t.DCOL_FETCHED] <= f[:, t.STEP_EMIT]).all()
    # a step sent ahead was called before the step before it was waited for
    ahead_of = numpy.flatnonzero(step & (late == 1))
    nxt = numpy.array([numpy.flatnonzero(step[i + 1:])[0] + i + 1
                       for i in ahead_of.tolist()])
    assert (rows[nxt, t.DCOL_RETURNED] <= rows[ahead_of, t.DCOL_WAIT]).all()


# ------------------------------------- (e) what the first counter counts
@pytest.mark.parametrize("kind", ["pre_ln", "window"])
def test_turns_prepared_ahead_counts_the_steps_that_follow_a_step(kind):
    """With nothing dropped, ``turns_prepared_ahead`` is ``decode_dispatches``
    less the decode turns that began with no step in flight (the first, and
    those behind a turn that only prefilled or waited)."""
    round_ = [(n, max(n_new, 2)) for n, n_new in ROUND]
    engine = make_engine(kind, name="count_" + kind, slots=2)
    serve(engine, round_)
    c = counters(engine)
    turns = engine.recorder.turns()
    step = turns[:, tracing.COL_STEP_PROGRAM] > 0
    cold = int((step & ~follows_a_step(turns)).sum())
    assert cold >= 1
    assert c.get("ahead_discarded", 0) == 0
    assert c["turns_prepared_ahead"] == c["decode_dispatches"] - cold
    # ISSUE 39: a step that follows a step goes out ahead of that one's
    # fetch; the steps no step follows are the drains
    assert pipeline_balances(engine)["dispatches_sent_ahead"] \
        == c["turns_prepared_ahead"]
    assert c["pipeline_drains"] == cold


# --------------------------------------- (c) arguments put ahead are copies
@pytest.mark.parametrize("kind", ["window", "linear"])
def test_an_argument_put_ahead_is_a_copy_and_keeps_its_value(kind,
                                                             monkeypatch):
    """A put may read host memory after it returns, and the loop now changes
    ``_pos``, ``_last`` and the tables in place while the arguments it put
    wait for their dispatch: nothing handed to ``xfer.to_device`` shares
    memory with them, and every argument of every prepared step still holds,
    after all traffic, what the host held when it was put."""
    from veles_tpu.serving import lm_engine
    engine = make_engine(kind, name="copies_" + kind)
    live = [engine._pos, engine._last, engine._page_tables, engine._decoding]
    if engine._wt is not None:
        live += [engine._wt.tables, engine._wt.base, engine._wt.count]
    real_put = lm_engine.xfer.to_device
    shared = []

    def put(x, dtype=None, device=None):
        if isinstance(x, numpy.ndarray) and any(
                numpy.shares_memory(x, a) for a in live):
            shared.append(x.shape)
        return real_put(x, dtype, device)
    monkeypatch.setattr(lm_engine.xfer, "to_device", put)
    kept = []
    real_prepare = engine._prepare_step

    def prepare(active):
        step = real_prepare(active)
        if step is not None:
            kept.append((step, engine._pos.copy(),
                         engine._page_tables[:, :step.width].copy(),
                         engine._decoding.copy()))
        return step
    engine._prepare_step = prepare
    serve(engine)
    assert not shared
    assert len(kept) > 10
    for step, pos, table, decoding in kept:
        numpy.testing.assert_array_equal(numpy.asarray(step.pos_dev), pos)
        arg = step.tables[0]
        if engine._state_shapes is not None:
            numpy.testing.assert_array_equal(numpy.asarray(arg[1]), decoding)
            arg = arg[0]
        elif engine._wt is not None:
            arg = arg[0][model_config.FULL]
        numpy.testing.assert_array_equal(numpy.asarray(arg), table)
    # and the lanes' state DID move under them
    assert any((pos != kept[0][1]).any() for _, pos, _, _ in kept[1:])


# ------------------- (d) what lands between preparation and dispatch
def gated(engine):
    """Hold the worker's first admission until the test has queued its
    requests, so that the turns are the same on every run."""
    gate = threading.Event()
    real = engine._admit_turn

    def admit_turn():
        assert gate.wait(60)
        return real()
    engine._admit_turn = admit_turn
    return gate


def after_stretch(engine, n, act):
    """Run ``act()`` at the end of the engine's ``n``-th early stretch: what
    it does lands between a preparation and its dispatch."""
    real, calls = engine._under_step, []

    def under_step(step, made_ahead):
        real(step, made_ahead)
        calls.append(1)
        if len(calls) == n:
            assert engine._ahead is not None
            # ISSUE 39: this step and the one before it are both unfetched
            assert sum(not f.first for f in engine._flights) == min(n, 2)
            act()
    engine._under_step = under_step


LANDINGS = ["step", "chunk", "tick", "cancel", "swap", "swap_finish",
            "checkpoint"]


@pytest.mark.parametrize("what", LANDINGS)
def test_what_lands_between_preparation_and_dispatch(what):
    """Two lanes: A decodes (a one-chunk prompt, 14 tokens), B prefills four
    chunks behind it, C waits in the queue.  A fault at ``engine.step``, at
    ``engine.chunk`` or at ``engine.tick``, a cancel of A or a weight swap
    that drains lands when a turn has been prepared under A's step: the
    preparation is dropped and counted, the lanes the event names fail (or
    are withdrawn, or decoded anew), the others' tokens are the greedy ones
    bit for bit, and the pool's books balance.  ISSUE 39: A's step before
    is unfetched then too (two dispatches in flight): its tokens are
    fetched first and reach their lanes; a swap that lets the lanes finish
    stamps them with the weights that made them; a checkpoint taken there
    holds what a fresh engine needs to serve the same tokens."""
    from veles_tpu.serving import FaultPlan, InjectedFault
    plan = FaultPlan(seed=0)
    if what == "step":
        plan.arm("engine.step", calls={3})
    elif what == "chunk":
        # turn 1 A's tail chunk, turn 2 B's first chunk, turn 3 B's second:
        # both of B's prepared under a step of A
        plan.arm("engine.chunk", calls={3})
    elif what == "tick":
        plan.arm("engine.tick", calls={4})
    engine = make_engine(name="lands_" + what, slots=2, faults=plan)
    gate = gated(engine)
    vocab = vocab_of(engine)
    a, b, c = (tokens(n, 70 + n, vocab) for n in (6, 29, 11))
    engine.start()
    try:
        fa, fb = engine.submit(a, 14), engine.submit(b, 5)
        fc = engine.submit(c, 6)
        swapped, states = [], []
        if what == "cancel":
            after_stretch(engine, 2, lambda: engine._cancel(fa.request))
        elif what == "checkpoint":
            after_stretch(engine, 2,
                          lambda: states.append(engine.checkpoint()))
        elif what.startswith("swap"):
            def swap():
                thread = threading.Thread(
                    target=lambda: swapped.append(engine.swap_weights(
                        jax.tree.map(jnp.array, engine.params),
                        drain=what == "swap")))
                thread.start()
                swapped.append(thread)
                while engine._peek_swap() is None:
                    assert thread.is_alive()
            after_stretch(engine, 2, swap)
        gate.set()
        failed = {"step": [fa], "chunk": [fb], "tick": [fa, fb]}.get(
            what, [])
        for name, f, prompt, n_new in (("a", fa, a, 14), ("b", fb, b, 5),
                                       ("c", fc, c, 6)):
            if f in failed:
                with pytest.raises(InjectedFault):
                    f.result(timeout=120)
            elif what == "cancel" and f is fa:
                # withdrawn in its slot: it leaves with the tokens it had
                assert 1 <= len(f.result(timeout=120)) < 14
            else:
                assert_greedy(engine, prompt, f.result(timeout=120), n_new)
        if what.startswith("swap"):
            swapped[0].join(60)
            assert not swapped[0].is_alive() and swapped[1] == 1
            # drained, all three decode anew on the new weights; left to
            # finish, A and B are the old weights' and C, held back, the new
            assert fc.version == 1
            assert fa.version == fb.version == (what == "swap")
    finally:
        engine.stop()
    assert engine.verify_pool_invariants()["used_pages"] == 0
    assert engine._ahead is None and not engine._undelivered
    cn = pipeline_balances(engine)
    assert cn.get("ahead_discarded", 0) >= (what != "checkpoint")
    assert cn.get("kv_storage_rebuilds", 0) == 0
    if what == "checkpoint":
        # taken with two dispatches in flight: the three requests, none
        # resolved; a fresh engine serves them the same tokens
        (state,) = states
        assert [len(r["prompt"]) for r in state["requests"]] == [6, 29, 11]
        fresh = make_engine(name="restored", slots=2).start()
        try:
            again = fresh.restore(state)
            for f, rid in zip((fa, fb, fc), sorted(again)):
                numpy.testing.assert_array_equal(
                    again[rid].result(timeout=120), f.result())
        finally:
            fresh.stop()
        assert fresh.verify_pool_invariants()["used_pages"] == 0
    outcomes = sorted(r.outcome for r in engine.recorder.requests())
    assert outcomes == sorted(["failed"] * len(failed)
                              + ["ok"] * (3 - len(failed)))
    # the recorder's stamps and the counter still agree, whatever failed
    assert sum(r.tokens_out for r in engine.recorder.requests()) \
        == cn["tokens_out"]


def test_a_failed_fetch_fails_the_lane_it_had_freed_by_count():
    """The step's program fails on the device: the lanes it advanced fail,
    the one that was freed by count when the step went out (its last) too,
    and a request admitted into that lane's slot under the step is served."""
    engine = make_engine(name="fetch", slots=1)
    gate = gated(engine)
    vocab = vocab_of(engine)
    a, b = tokens(5, 1, vocab), tokens(7, 2, vocab)
    boom = RuntimeError("the step failed on the device")
    real, calls = engine._under_step, []

    def under_step(step, made_ahead):
        real(step, made_ahead)
        calls.append(1)
        if len(calls) == 2:     # A's second and last step
            raise boom
    engine._under_step = under_step
    engine.start()
    try:
        fa, fb = engine.submit(a, 3), engine.submit(b, 4)
        gate.set()
        with pytest.raises(RuntimeError, match="failed on the device"):
            fa.result(timeout=120)
        # B took A's slot under A's last step; the storage went down with
        # the failed dispatch and took B's rows with it
        with pytest.raises(RuntimeError, match="failed on the device"):
            fb.result(timeout=120)
        again = engine.submit(b, 4).result(timeout=120)
        assert_greedy(engine, b, again, 4)
    finally:
        engine.stop()
    assert engine.verify_pool_invariants()["used_pages"] == 0
    assert counters(engine)["kv_storage_rebuilds"] == 1


# ----------------------------------------- ISSUE 39: two dispatches in flight
@pytest.mark.parametrize("n_new", [1, 2])
@pytest.mark.parametrize("kind", ["pre_ln", "window", "latent", "linear"])
def test_a_tail_chunks_first_token_may_be_its_requests_last(kind, n_new):
    """Short answers beside a long one: with ``n_new`` 1 the tail chunk's
    token, which never leaves the device before the next step reads it, is
    the whole answer and the lane is freed by count at the chunk's call;
    with 2 the lane is freed under its only step.  Every token is the
    reference's, each request's first token is stamped once, when it is
    fetched, and nothing is left in flight."""
    round_ = [(5, 12), (19, n_new), (3, n_new), (26, n_new), (9, n_new)]
    engine = make_engine(kind, name="short%d_%s" % (n_new, kind), slots=2)
    prompts, outs = serve(engine, round_)
    for p, o, (_, n) in zip(prompts, outs, round_):
        check_tokens(kind, engine, p, o, n)
    assert engine.verify_pool_invariants()["used_pages"] == 0
    assert engine._ahead is None and not engine._undelivered
    c = pipeline_balances(engine)
    assert c["tokens_out"] == sum(n for _, n in round_)
    assert c.get("kv_storage_rebuilds", 0) == 0
    assert engine.metrics.snapshot()["ttft"]["count"] == len(round_)
    reqs = engine.recorder.requests()
    assert sorted(r.tokens_out for r in reqs) == sorted(n for _, n in round_)
    assert all(r.outcome == "ok" and r.first_token == r.token_ns[0]
               for r in reqs)


def test_a_fetch_that_raises_fails_the_younger_dispatch_too(monkeypatch):
    """Two lanes: A decodes ten tokens, B three.  The fetch of the step that
    is B's last raises while A's next step is already in flight: that one
    read what the failed one wrote, so the lanes of BOTH fail (A in its
    slot, B freed by count under the failed step), C, admitted into B's slot
    under it, with them; the storage is rebuilt, the books balance, nothing
    stays in flight and the engine serves the next request."""
    from veles_tpu.serving import lm_engine
    engine = make_engine(name="late_fetch", slots=2)
    gate = gated(engine)
    vocab = vocab_of(engine)
    a, b, c = (tokens(n, 90 + n, vocab) for n in (5, 7, 6))
    boom = RuntimeError("the step failed on the device")
    real, fired = lm_engine.xfer.to_host, []

    def to_host(x):
        flights = engine._flights
        if not fired and flights and x is flights[0].outs \
                and not flights[0].first and any(flights[0].lasts):
            # B's last step, and a younger one behind it
            assert sum(not f.first for f in flights) == 2
            fired.append(1)
            raise boom
        return real(x)
    monkeypatch.setattr(lm_engine.xfer, "to_host", to_host)
    engine.start()
    try:
        fa, fb = engine.submit(a, 10), engine.submit(b, 3)
        fc = engine.submit(c, 4)
        gate.set()
        for f in (fa, fb, fc):
            with pytest.raises(RuntimeError, match="failed on the device"):
                f.result(timeout=120)
        again = engine.submit(c, 4).result(timeout=120)
        assert_greedy(engine, c, again, 4)
    finally:
        engine.stop()
    assert fired
    assert engine.verify_pool_invariants()["used_pages"] == 0
    assert not engine._flights and engine._ahead is None \
        and not engine._undelivered
    assert counters(engine)["kv_storage_rebuilds"] == 1
    outcomes = sorted(r.outcome for r in engine.recorder.requests())
    assert outcomes == ["failed"] * 3 + ["ok"]
