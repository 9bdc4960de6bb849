"""idle_share.serve: 1 - (union of device-op intervals) / traced serving
window, in %, from the profiler trace; the mean over the chips used."""
from bench.tracing import idle_share


def read(run):
    if run.get("kind") != "serve" or not run["trace"].devices:
        return None
    shares = [idle_share(ops, run["lo"], run["hi"])
              for ops in run["trace"].devices.values()]
    return sum(shares) / len(shares)
