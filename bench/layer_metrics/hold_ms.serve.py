"""Batcher: time the drain held a partial batch open for more arrivals, per
served step (``BatchedServer.stats`` hold_s over batches, traced slice)."""


def read(r: dict):
    st = r.get("server_stats")
    if not st or "hold_s" not in st or not st["batches"]:
        return None
    return 1e3 * st["hold_s"] / st["batches"]
