"""topk_roofline.serve: the dense top-k decode's roofline time over the
traced serving window's microbatches (bench/decode_roofline.py: the
table read once a microbatch, at the chip's HBM bandwidth) over the
device's busy time in the window, in %, the mean over chips."""
from bench.decode_roofline import window_roofline_seconds
from bench.tracing import busy_ns


def read(run):
    if (run.get("kind") != "serve" or not run.get("microbatches")
            or not run["trace"].devices or "peak_bytes_per_s" not in run):
        return None
    busy = [busy_ns(ops, run["lo"], run["hi"]) / 1e9
            for ops in run["trace"].devices.values()]
    busy_s = sum(busy) / len(busy)
    if busy_s <= 0:
        return None
    least = window_roofline_seconds(
        run["microbatches"], run["batch_slots"], run["n"], run["d"],
        run["k"], run["peak_flops"], run["peak_bytes_per_s"],
        run["table_itemsize"])
    return 100.0 * least / busy_s
