"""Host staging: the share of the traced window in which the host is
inside a ``stage.pace`` span (waiting for the step that consumed block
b-1 before it issues block b+1's copy) and the chip runs no operation,
in %, averaged over the chips.  Reads the program's host spans and the
device trace, as ``stage_h2d_share`` does.
"""
from bench import harness


def read(run):
    return harness.load_metric("stage_h2d_share", run.root).idle_under(
        run, "stage.pace")
