"""How uneven a labelled counter's series grew over the window, in %:
(largest delta / mean delta - 1) x 100 over the series of
``params["counter"]`` (one a label: an expert, a replica). 0 is even.
No such counter, or nothing counted: nothing returned."""


def read(record: dict, params: dict):
    m = (record.get("registry") or {}).get(params["counter"])
    if not m:
        return None
    deltas = [s["delta"] for label, s in m["series"].items() if label]
    if not deltas or not sum(deltas):
        return None
    return 100.0 * (max(deltas) / (sum(deltas) / len(deltas)) - 1.0)
