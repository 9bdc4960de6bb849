"""decode_ms.serve: device busy time in the traced serving window over the
microbatches the engine launched in it (the delta of its ``microbatches``
counter), in ms, the mean over chips."""
from bench.tracing import busy_ns


def read(run):
    if (run.get("kind") != "serve" or not run.get("microbatches")
            or not run["trace"].devices):
        return None
    busy = [busy_ns(ops, run["lo"], run["hi"])
            for ops in run["trace"].devices.values()]
    return sum(busy) / len(busy) / 1e6 / run["microbatches"]
