"""device_ms.train.head_loss: device self time of the train step's
``head_loss`` scope per window step, in ms, the mean over chips, from
the profiler trace and the compiled step's HLO text (bench/scopes.py)."""
from bench.scopes import device_ms


def read(run):
    return device_ms(run, "head_loss")
