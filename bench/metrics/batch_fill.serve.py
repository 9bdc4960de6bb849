"""batch_fill.serve: the rows of real requests over the rows the engine's
microbatches held (padding to the bucket included) in the traced window,
in %, from the deltas of its ``batch_real`` and ``batch_slots``
counters."""


def read(run):
    if run.get("kind") != "serve" or not run.get("batch_slots"):
        return None
    return 100.0 * run["batch_real"] / run["batch_slots"]
