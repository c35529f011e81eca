"""Idle share of the device in the traced window, % (see ``xplane.py``)."""

from xplane import idle_share


def read(record):
    return idle_share(record)
