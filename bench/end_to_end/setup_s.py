"""Set-up: process start to the first timed op (data, build, warm-up)."""


def read(r: dict):
    return r["setup_s"]
