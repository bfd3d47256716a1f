"""Deletes + inserts acknowledged over the window's whole steps, over the
time those steps took."""


def read(r: dict):
    if "items" not in r:
        return None
    return r["items"] / r["window_s"]
