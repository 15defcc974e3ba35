"""Solver driver: mean block iterations per solve that did not fail
(``SVDResult.iters``, the driver's counter)."""


def read(run):
    done = [s.iters for s in run.solves if s.ok]
    return sum(done) / len(done) if done else None
