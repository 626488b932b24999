"""Windowed mean of one series of a histogram of the program's registry
(``delta_sum / delta_count`` over the measured window), in ms."""


def read(record: dict, params: dict):
    m = record.get("registry", {}).get(params["metric"])
    s = m and m["series"].get(params.get("labels", ""))
    if not s or not s.get("delta_count"):
        return None
    return 1e3 * s["delta_sum"] / s["delta_count"]
