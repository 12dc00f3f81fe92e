"""Fits the window ran: a metric added as a reader file of its own."""


def read(record):
    fits = record.get("fits")
    return float(len(fits)) if fits else None
