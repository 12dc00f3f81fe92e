"""Share of the traced fit window in which the device ran no operation:
1 - (union of the device's operation intervals / window), in percent."""


def read(record):
    tr, w = record.get("trace"), record.get("window")
    if tr is None or w is None or not record.get("fits"):
        return None
    share = tr.idle_share(*w)
    return None if share is None else 100.0 * share
