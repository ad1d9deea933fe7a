"""Engine: share of batch slots that held a request."""
import readers


def read(run):
    delta, _, _ = readers.span(run)
    n = delta.get("engine.batched_requests", 0)
    return readers.share(n, n + delta.get("engine.padded_slots", 0))
