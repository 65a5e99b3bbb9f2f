"""Rows that carried a request over rows executed, in %: the engine
pads every batch to its ``max_batch`` bucket (the program's counter
``engine.max_batch``; one row per finished request)."""


def read(run):
    b = run.win.batches
    if not b:
        return None
    return 100.0 * sum(x["rows"] for x in b) / (len(b) * run.mix["max_batch"])
