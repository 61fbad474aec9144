"""Seconds from process start to the window's start: data, compile or
cache load, warm-up (host clock)."""


def read(record):
    return record["setup_s"]
