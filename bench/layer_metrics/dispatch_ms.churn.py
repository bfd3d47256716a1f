"""Host dispatch: ``PhaseTimers`` insert_s + delete_s over the window, per
dispatched op step."""


def read(r: dict):
    if not r.get("dispatch_chunks"):
        return None
    return 1e3 * r["dispatch_s"] / r["dispatch_chunks"]
