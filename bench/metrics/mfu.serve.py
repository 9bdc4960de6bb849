"""mfu.serve: the model FLOPs of the requests served in the traced serving
window, 2 x n x d each (one row of scores over the whole table; padding
not counted), over (the window's time on the host clock x chips x the
chip's peak), in %."""


def read(run):
    if run.get("kind") != "serve" or not run.get("seconds"):
        return None
    flops = 2.0 * run["n"] * run["d"] * run["completed"]
    return 100.0 * flops / (run["seconds"] * run["chips"]
                            * run["peak_flops"])
