"""Enclave: host milliseconds per request of the enclave's unseal (the
program's ``unseal`` spans: MAC check and decryption of a batch's sealed
requests, run one after another on the batcher thread)."""
import readers


def read(run):
    spans = readers.program_spans(run, "unseal")
    n = sum(s.attrs.get("n_requests", 0) for s in spans)
    if n == 0:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in spans) / n
