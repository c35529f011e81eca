"""Requests completed in the window over the window's length, which runs
from its start to the end of the last round that began inside it."""


def read(record):
    if not record.get("window_s"):
        return None
    return record["completed_in_window"] / record["window_s"]
