"""Sessions: share of blinding sessions handed out before the pool had
their factors ready."""
import readers


def read(run):
    delta, _, _ = readers.span(run)
    return readers.share(delta.get("pool.misses", 0),
                         delta.get("pool.consumed", 0))
