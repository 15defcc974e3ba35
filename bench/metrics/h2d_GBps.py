"""Host staging: bytes the solves copied host to device
(``SVDResult.bytes_moved["host"]``, the operator's counter) over the
solves' host-clock seconds, in GB/s.
"""


def read(run):
    done = [s for s in run.solves if s.ok and s.host_bytes]
    if not done:
        return None
    return sum(s.host_bytes for s in done) / sum(s.wall_s for s in done) / 1e9
