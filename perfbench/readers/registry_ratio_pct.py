"""A ratio of counter deltas of the program's registry over the window,
in %: ``num`` and ``den`` are lists of ``[counter, weight]``; ``den`` is
multiplied by ``record[params["den_times"]]`` where that is given."""


def _sum(reg: dict, terms) -> float | None:
    total = 0.0
    for name, w in terms:
        m = reg.get(name)
        if not m or "" not in m["series"]:
            return None
        total += w * m["series"][""]["delta"]
    return total


def read(record: dict, params: dict):
    reg = record.get("registry")
    if not reg:
        return None
    num, den = _sum(reg, params["num"]), _sum(reg, params["den"])
    if num is None or not den:
        return None
    if params.get("den_times"):
        den *= record[params["den_times"]]
    return 100.0 * num / den
