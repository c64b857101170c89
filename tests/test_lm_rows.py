"""The loop recorder's row of a turn, per decode driver and per kind of
engine the benchmark's cells run (ISSUES 37, 39; moved here from
``test_lm_ahead.py``, which rides one worker for twelve minutes): what
``benchmark/lib/spans.py`` reads keeps its shape whatever a lane holds (a
pool of latent rows, a slot of recurrent state, the drafting module's
pool) and whichever driver turns the loop.

No case asserts a duration: only order, counts, identities and tokens."""

import numpy
import pytest

from lm_cases import (ROUND, check_tokens, counters, follows_a_step,
                      make_engine, pipeline_balances, serve, stamps_of)
from veles_tpu.serving import tracing

DRIVERS = {
    "plain": dict(),
    "plain_window": dict(kind="window"),
    "speculative": dict(spec_k=2),
    "megastep": dict(megastep=4),
    "plain_latent": dict(kind="latent"),
    "plain_linear": dict(kind="linear"),
    "plain_mtp": dict(kind="mtp"),
}


@pytest.mark.parametrize("driver", sorted(DRIVERS))
def test_a_turns_row_keeps_its_shape(driver):
    """What ``benchmark/lib/spans.py`` relies on, per driver: stamps never
    go back, turns leave no hole, a row holds at most one chunk and one
    step with ``prefill.dispatch`` <= ``step.dispatch`` <= ``step.emit``,
    every phase has its name in ``tracing.PHASES``; the ``ahead.*`` phases
    are empty where no step is in flight and for every driver that cannot
    split its turn; token stamps number ``n_new`` a request and sum to
    ``tokens_out``."""
    kw = dict(DRIVERS[driver])
    kind = kw.pop("kind", "pre_ln")
    round_ = [(n, max(n_new, 2)) for n, n_new in ROUND]
    engine = make_engine(kind, name="row_" + driver, **kw)
    prompts, outs = serve(engine, round_)
    for p, o, (_, n_new) in zip(prompts, outs, round_):
        check_tokens(kind, engine, p, o, n_new)
    rec, c = engine.recorder, counters(engine)
    turns = rec.turns()
    s = stamps_of(turns)
    assert (numpy.diff(s, axis=1) >= 0).all()
    assert (s[1:, 0] == s[:-1, -1]).all()
    assert turns[:, tracing.COL_SEQ].tolist() == list(range(1, len(turns) + 1))
    assert len(tracing.PHASES) == s.shape[1] - 1
    assert tracing.PHASES.index("step.dispatch") \
        < tracing.PHASES.index("ahead.emit") \
        < tracing.PHASES.index("ahead.admit") \
        < tracing.PHASES.index("ahead.prepare") \
        < tracing.PHASES.index("step.fetch") \
        < tracing.PHASES.index("step.emit")
    assert {e["name"] for e in rec.chrome_events(1, last=len(turns))
            if e["ph"] == "X" and e["tid"] == 1} <= set(tracing.PHASES)
    step = turns[:, tracing.COL_STEP_PROGRAM] > 0
    chunk = turns[:, tracing.COL_PREFILL_PROGRAM] > 0
    assert int(step.sum()) == c["decode_dispatches"]
    assert int(chunk.sum()) == c.get("prefill_dispatches", 0)
    assert (s[:, tracing.PREFILL_DISPATCH] <= s[:, tracing.STEP_DISPATCH]).all()
    assert (s[:, tracing.STEP_DISPATCH] <= s[:, tracing.STEP_EMIT]).all()
    ahead = s[:, tracing.STEP_FETCH] - s[:, tracing.AHEAD_EMIT]
    assert not ahead[~step].any()
    splits = driver.startswith("plain")
    rows = rec.dispatches()
    of_step = rows[:, tracing.DCOL_PHASE] == tracing.STEP_DISPATCH
    late = rows[:, tracing.DCOL_FETCH_TURN] - rows[:, tracing.DCOL_TURN]
    assert (rows[:, tracing.DCOL_FETCHED] > 0)[of_step].all()
    if splits:
        # ISSUE 39: a step whose follower was sent ahead of its fetch is
        # fetched in the follower's turn, a step that was drained in its own
        c = pipeline_balances(engine)
        assert int((late[of_step] == 1).sum()) \
            == c["dispatches_sent_ahead"] > c["decode_dispatches"] // 2
        assert int((late[of_step] == 0).sum()) == c["pipeline_drains"]
        # and a tail chunk's token rides with the step behind it
        assert set(late[~of_step & (rows[:, tracing.DCOL_FETCHED] > 0)]
                   .tolist()) <= {0, 1}
    else:
        assert not late[rows[:, tracing.DCOL_FETCHED] > 0].any()
        assert "dispatches_sent_ahead" not in c and "pipeline_drains" not in c
    if splits:
        assert (ahead[step] > 0).all()
        # a turn prepared under the step before skips admission and the
        # chunk's preparation: the tick runs into the first dispatch
        made = follows_a_step(turns) & step
        assert int(made.sum()) == c["turns_prepared_ahead"] > 0
        assert (s[made, tracing.ADMIT]
                == s[made, tracing.PREFILL_DISPATCH]).all()
        assert (s[made & ~chunk, tracing.ADMIT]
                == s[made & ~chunk, tracing.STEP_PREPARE]).all()
    else:
        assert not ahead.any()
        assert "turns_prepared_ahead" not in c
    assert c.get("ahead_discarded", 0) == 0
    reqs = rec.requests()
    assert len(reqs) == len(round_)
    for r, (_, n_new) in zip(sorted(reqs, key=lambda r: r.enqueue), round_):
        assert r.outcome == "ok"
        assert r.tokens_out == len(r.token_ns) == r.n_new == n_new
        assert list(r.token_ns) == sorted(r.token_ns)
        assert r.enqueue <= r.admit <= r.first_token <= r.done
    assert sum(r.tokens_out for r in reqs) == c["tokens_out"] \
        == int(turns[:, tracing.COL_TOKENS].sum())
