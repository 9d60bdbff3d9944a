"""Process start to the moment the window opens (the first request due)."""


def read(record):
    return record["setup_s"]
