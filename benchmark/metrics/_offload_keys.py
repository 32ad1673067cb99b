"""What the keyed offload tier's readers share: the window's counters and
span seconds come from ``run["offload"]`` as the bounded tier's do
(``_offload.py``); a keyed store's gauges from ``run["offload_store"]``
(``train_offload_keys_runner`` reads the tier's own ledger at the window's
end). A program without them gives every reader nothing to read: ``None``,
never a raise."""



def counter(run, name):
    """The window's count of one of the tier's counters, both tables; None
    where the run has no tier or the program no such counter."""
    tier = run.get("offload")
    if not tier or not tier.get("offload_unique_rows") or name not in tier:
        return None
    return tier[name]


def store_gauge(run, name):
    """{table: value} of a keyed store's gauge at the window's end; None
    where the program's tier has none."""
    store = run.get("offload_store") or {}
    out = {t: g.get(name) for t, g in store.items()}
    return out if out and all(v is not None for v in out.values()) else None

