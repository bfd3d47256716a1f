"""Batcher: requests per op step over the step's width
(``BatchedServer.stats`` over the traced slice)."""


def read(r: dict):
    st = r.get("server_stats")
    if not st or not st["batches"]:
        return None
    return 100.0 * st["requests"] / (st["batches"] * r["max_batch"])
