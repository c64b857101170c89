"""Driver for a served language model that DRAFTS WITH ITS OWN MODULE
(multi-token prediction): ``serve_lm_record.Driver`` with the launcher's
``--serve-spec-k`` among the keywords (``deployment["spec_k"]``), clients that
ask for the drafts with the tokens (``"drafts": true``), and three numbers
more in what ``correct`` compares:

- ``mtp_drafts_off_share``: the share (%) of the DRAFTS the program made for
  the checked requests, accepted or not, that are not what the REFERENCE's
  module, teacher-forced on the served sequence, puts first at the same row
  (their logit under that module lies more than a roundoff tie under its
  best).  Speculation is lossless whatever is drafted, so a module that lost
  a norm, or whose halves are swapped, leaves every served token right: this
  is where it shows.  Its control reading is the reference's module computed
  in the control precision, put in the program's place.
- ``mtp_accept_gap``: percentage points between the share of drafts the
  program accepted in the window (``draft_accepted / draft_tokens`` of
  ``/metrics.json``, at the window's first and last samples) and the share of
  positions at which the reference's module puts first the token served two
  places on (``reference.draft_hits``).  The program drafts at the ends of
  its steps only (behind an accepted draft it skips a position) and over
  every request of the window, the reference at every position of the
  checked ones: the two agree closely, not exactly.  Right drafts compared
  with the wrong row show here, and nowhere else.
- ``spec_steps_share``: the decode dispatches of the window that verified a
  draft over all of them, compared as its SHORTFALL from 100 % (the harness
  holds every compared number UNDER its limit): a run that turned speculation
  off is not ``correct``."""

from __future__ import annotations

import json
import sys
import time
import urllib.error
import urllib.request

import numpy

from benchmark.lib import client as client_lib
from benchmark.lib.files import load_module
from benchmark.lib.window import counters_moved

record = load_module("drivers", "serve_lm_record")


class WithDrafts(record.WithConfig):
    """``WithConfig`` that also asks the reference, for every sequence it is
    given, where its module would have hit and how far under that module's
    best the drafts lie that the program made for it (the stack's pass is
    shared)."""

    def __init__(self, reference, cfg):
        super().__init__(reference, cfg)
        self.drafts_of = {}            # served tokens -> the drafts made
        self.begin()

    def begin(self):
        self.hits = self.positions = 0
        self.drafts, self.drafts_lowered = [], []

    def token_gaps(self, weights, tokens, first, heads, pad_to, rows_to,
                   control=None):
        out = super().token_gaps(weights, tokens, first, heads, pad_to,
                                 rows_to, control=control)
        hits, positions = self.reference.draft_hits(
            weights, tokens, first, self.cfg, pad_to, rows_to)
        self.hits += hits
        self.positions += positions
        made, low = self.reference.draft_gaps(
            weights, tokens, first, self.drafts_of.get(tuple(tokens), ()),
            self.cfg, pad_to, rows_to, control=control)
        self.drafts.append(made)
        if low is not None:
            self.drafts_lowered.append(low)
        return out


class WithDraftsAsked(record.InOrder):
    """The closed loop whose requests ask for the drafts (``"drafts":
    true``) and whose records keep them beside the tokens (``drafts``:
    ``[[n, token], ...]``; the base client keeps ``tokens`` alone): its
    ``_client`` with those two things more."""

    def _client(self, c):
        timeout = float(self.traffic["request_timeout_s"])
        index = 0
        while not self._stop.is_set():
            entry, length, n_new = client_lib.request_sizes(
                self.traffic, c, index)
            prompt = client_lib.prompt_tokens(self.seed, c, index, length,
                                              self.vocab)
            req = urllib.request.Request(
                self.url, data=json.dumps({
                    "input": [prompt], "n_new": n_new,
                    "drafts": True}).encode(),
                headers={"Content-Type": "application/json"})
            rec = {"client": c, "index": index, "entry": entry,
                   "prompt_len": length, "n_new": n_new,
                   "t_send": time.monotonic(), "t_done": None,
                   "code": None, "class": None, "tokens": None,
                   "drafts": None}
            with self._lock:
                self.log.append(rec)
            out, code, exc = {}, 0, None
            try:
                with urllib.request.urlopen(req, timeout=timeout) as resp:
                    out = json.loads(resp.read())
                    code = resp.status
            except urllib.error.HTTPError as e:
                code = e.code
            except Exception as e:  # noqa: BLE001 — connection-level
                exc = e
            done = time.monotonic()

            def row(key):
                rows = out.get(key) if code == 200 else None
                return rows[0] if isinstance(rows, list) \
                    and len(rows) == 1 else None
            with self._lock:
                rec.update(t_done=done, code=code,
                           **{"class": client_lib.failure_class(code, exc)},
                           tokens=row("tokens"), drafts=row("drafts"),
                           prompt=prompt)
            index += 1


def launcher_keywords(deployment):
    from veles_tpu.__main__ import build_argparser
    a = build_argparser().parse_args(
        ["workflow", "--serve", "0",
         "--serve-spec-k", str(deployment["spec_k"])])
    return dict(record.launcher_keywords(deployment), spec_k=a.serve_spec_k)


class Driver(record.Driver):
    def __init__(self, ctx):
        super().__init__(ctx)
        self.reference = WithDrafts(self.reference.reference, self.cfg)

    def setup(self):
        """``serve_lm_record.Driver.setup`` with ``spec_k`` among the
        keywords."""
        from veles_tpu import compile_cache
        from veles_tpu.restful_api import serve_lm
        compile_cache.enable()
        wf = self.make_workflow()
        deployment = self.cfg["deployment"]
        self.api = serve_lm(wf, deadline_s=deployment["deadline_s"],
                            **launcher_keywords(deployment))
        self.base = "http://127.0.0.1:%d" % self.api.port
        self.clients = WithDraftsAsked(
            self.base + "/predict", self.traffic, self.ctx.seed,
            self.cfg["vocab_size"]).start()
        time.sleep(float(self.traffic["lead_s"]))

    def check(self, art, control=None):
        self.reference.begin()
        self.reference.drafts_of = {
            tuple(r["tokens"]): r["drafts"] or () for r in art["ok"]}
        compared = super().check(art, control=control)
        limits = self.cfg["limits"]

        def off_share(gaps):
            every = numpy.concatenate(gaps)
            return (100.0 * float((every > record.TIE).mean())
                    if len(every) else None)

        compared["mtp_drafts_off_share"] = {
            "value": off_share(self.reference.drafts),
            "limit": limits["mtp_drafts_off_share"]}
        if self.reference.drafts_lowered:
            art["control"]["mtp_drafts_off_share"] = off_share(
                self.reference.drafts_lowered)
        moved = counters_moved(art)
        drafts, steps = moved.get("draft_tokens"), moved.get(
            "decode_dispatches")
        gap = None
        if drafts and self.reference.positions:
            gap = abs(100.0 * moved.get("draft_accepted", 0) / drafts
                      - 100.0 * self.reference.hits
                      / self.reference.positions)
        compared["mtp_accept_gap"] = {
            "value": gap, "limit": limits["mtp_accept_gap"]}
        compared["spec_steps_share"] = {
            "value": (100.0 - 100.0 * moved.get("spec_dispatches", 0) / steps
                      if steps else None),
            "limit": limits["spec_steps_share"]}
        art["drafts"] = {"accepted": moved.get("draft_accepted"),
                         "drafted": drafts,
                         "checked": int(sum(
                             len(g) for g in self.reference.drafts)),
                         "reference_hits": self.reference.hits,
                         "reference_positions": self.reference.positions}
        print("drafts: %r" % (art["drafts"],), file=sys.stderr)
        return compared
