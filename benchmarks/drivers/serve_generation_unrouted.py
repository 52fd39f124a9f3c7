"""``serve_generation`` for a model in which no token chooses an expert.

The driver, its dispatcher, window, drain and reference sample are
``serve_generation``'s own (``ctx.module("drivers", "serve_generation")``
and its ``run``).  One thing differs.  Its verdict nests the MATCHED
comparison — the served log-probabilities must lie nearer the full
reference's than a lowered one's, ``tolerance.matched`` — under "some
routing was told" (``_Compared.verdict``: ``if self.told``), because the
one model it served had both.  A replay of a model without experts tells
nothing (``routed_experts`` has no entries), the squared distances are
still gathered, and no check is made of them.  Here they are checked, by
the same arithmetic under the same names (``nearer_full_than_<precision>``
in ``checks``; ``nearer_full_than``, ``nearer_full_than_limits`` and
``logprob_rms_apart`` in ``facts``).  Moving that gate in
``serve_generation.py`` would retire this file (PERF.md section 7: a
``benchmark`` issue's edit)."""
from __future__ import annotations


def run(ctx):
    base = ctx.module("drivers", "serve_generation")

    class Compared(base._Compared):
        def verdict(self, absmax=None):
            checks, facts = super().verdict(absmax)
            lowered = {how: apart for how, apart in self.apart.items()
                       if how is not None}
            if self.told or not lowered or not self.gaps:
                return checks, facts
            full = self.apart[None]
            limits = self.tol["matched"]
            facts.update(
                logprob_rms_apart=(full / len(self.gaps)) ** 0.5,
                nearer_full_than={}, nearer_full_than_limits={
                    how: limits[how] for how in lowered})
            for how, apart in lowered.items():
                # > 1: nearer the full reference than the lowered one
                ratio = apart / full if full else None
                facts["nearer_full_than"][how] = ratio
                checks["nearer_full_than_" + how] = \
                    ratio is None or ratio >= limits[how]
            return checks, facts

    base._Compared = Compared
    return base.run(ctx)
