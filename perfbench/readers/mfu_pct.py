"""The whole step's share of the chip's peak: the FLOPs the window's work
needs (``perfbench/counts.py``; no recomputation, no embedding lookup)
over the window's wall time and the published peak of every chip used."""


def read(record: dict, params: dict):
    peak, trace = record.get("peak"), record.get("trace")
    if not peak or not record.get("flops"):
        return None
    chips = trace["chips"] if trace else 1
    return 100.0 * record["flops"] / record["window_s"] / (peak["flops"] * chips)
