"""Mean duration of one of the benchmark's own host spans, in ms."""


def read(record: dict, params: dict):
    d = record.get("spans", {}).get(params["span"])
    return 1e3 * sum(d) / len(d) if d else None
