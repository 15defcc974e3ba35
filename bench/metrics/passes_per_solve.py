"""Solver driver: mean passes over ``A`` per solve that did not fail
(``SVDResult.passes_over_A``, the operator's counter)."""


def read(run):
    done = [s.passes for s in run.solves if s.ok]
    return sum(done) / len(done) if done else None
