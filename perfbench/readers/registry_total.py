"""The cumulative ``sum`` of one histogram series of the program's
registry as the window's closing snapshot holds it, in the histogram's own
unit: for what is observed before the window's deltas start (warm-up)."""


def read(record: dict, params: dict):
    m = record.get("registry", {}).get(params["metric"])
    s = m and m["series"].get(params.get("labels", ""))
    if not s or not s.get("count"):
        return None
    return s["sum"]
