"""One of the numbers the serve driver takes on the clients' side
(``record["client"]``), for a cell in which it is no end-to-end metric."""


def read(record: dict, params: dict):
    return record.get("client", {}).get(params["key"])
