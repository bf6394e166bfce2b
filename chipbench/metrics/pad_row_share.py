"""Share of the input-row slots ``FeatureStore.fetch_masked`` filled with
zero rows (the sampler's padding), in % (program span
``repro.store.fetch_masked``, attributes ``pad_rows`` and ``rows``)."""
from chipbench import program_trace as P


def read(run):
    fetches = P.spans(run, __file__, "store.fetch_masked")
    rows = sum(int(s.attrs["rows"]) for s in fetches)
    if not rows:
        return None
    return 100.0 * sum(int(s.attrs["pad_rows"]) for s in fetches) / rows
