"""1 - (union of the device's operation intervals) / (traced window)."""


def read(record: dict, params: dict):
    t = record.get("trace")
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
