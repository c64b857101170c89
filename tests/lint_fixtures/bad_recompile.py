"""Seeded recompile-hazard violations (ISSUE 17).

Every marked line must be flagged at exactly that line: traced-body
closure over ``self``, shape-dependent Python branching, Python
concretization of a traced argument, a jit site with no program-family
census entry, and a census entry that lies about its family.  The
census cross-check findings land in ``jitguard_fixture.py`` (the
stand-in jit-guard file), marked there.
"""


class FakeEngine:
    def _jit(self, fn):
        return fn

    def _build(self):
        def step(x, pos):
            if x.shape[0] > 4:                 # EXPECT-LINT recompile-hazard
                x = x + 1
            k = int(pos)                       # EXPECT-LINT recompile-hazard
            return x * self.scale + k          # EXPECT-LINT recompile-hazard

        self._step_jit = self._jit(step)       # EXPECT-LINT recompile-hazard
        # programs: twin
        self._decode_jit = self._jit(step)     # EXPECT-LINT recompile-hazard
        # programs: verify
        self._verify_jit = self._jit(step)     # EXPECT-LINT recompile-hazard
