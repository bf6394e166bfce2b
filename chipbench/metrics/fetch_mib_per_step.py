"""``FeatureStore.transferred_bytes`` over the window, per step, in MiB."""


def read(run):
    steps = run.window["steps"]
    fetched = run.window["counters"].get("fetch_bytes")
    if not steps or fetched is None:
        return None
    return fetched / steps / 2**20
