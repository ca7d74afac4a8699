"""Seconds from process start to the first timed request: imports, data
generation, capture, session and tier start, warm-up and compilation."""


def read(record):
    return record["setup_s"]
