"""Sessions: share of the factor sets batches took that the batch had to
make itself while its requests waited (the program's
``BlindedLayerCache.take`` counters, summed in ``SessionPool.stats``)."""
import readers


def read(run):
    delta, _, _ = readers.span(run)
    if "pool.factors_taken" not in delta:
        return None
    return readers.share(delta.get("pool.factors_sync", 0),
                         delta["pool.factors_taken"])
