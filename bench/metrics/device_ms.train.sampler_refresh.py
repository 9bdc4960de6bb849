"""device_ms.train.sampler_refresh: device self time of the train step's
``sampler_refresh`` scope per window step, in ms, the mean over chips, from
the profiler trace and the compiled step's HLO text (bench/scopes.py)."""
from bench.scopes import device_ms


def read(run):
    return device_ms(run, "sampler_refresh")
