"""Operations and bytes of the dense top-k decode, and its roofline time.

One microbatch of ``bucket`` hidden states (d floats each) scored against
an (n, d) table, its top k kept.  The least any implementation must do:

  bytes  the table read once (n * d * table itemsize), the hidden states
         read (bucket * d * 4) and the answers written (bucket * k * 8:
         an int32 id and a float32 logit each); the (bucket, n) logits
         need never reach HBM
  FLOPs  2 * bucket * n * d, the products and sums of the scores

Roofline time is the larger of bytes over the chip's HBM bandwidth and
FLOPs over its peak (``bench/peaks.py``).  At the serving buckets the
decode is bound by memory: the table alone takes n * d * 4 / 819e9 = 1.25
ms on a v5e at n = 1M, d = 256, against 2.66 us of FLOPs a row.
"""
from __future__ import annotations

#: an int32 id and a float32 logit per answer
ANSWER_BYTES = 8
HIDDEN_BYTES = 4


def decode_bytes(bucket: int, n: int, d: int, k: int,
                 table_itemsize: int = 4) -> float:
    return (float(n) * d * table_itemsize + float(bucket) * d * HIDDEN_BYTES
            + float(bucket) * k * ANSWER_BYTES)


def decode_flops(bucket: int, n: int, d: int) -> float:
    return 2.0 * bucket * n * d


def memory_bound(bucket: int, n: int, d: int, k: int, peak_flops: float,
                 peak_bytes_per_s: float, table_itemsize: int = 4) -> bool:
    return (decode_bytes(bucket, n, d, k, table_itemsize) / peak_bytes_per_s
            >= decode_flops(bucket, n, d) / peak_flops)


def roofline_seconds(bucket: int, n: int, d: int, k: int, peak_flops: float,
                     peak_bytes_per_s: float,
                     table_itemsize: int = 4) -> float:
    """The least time one microbatch of ``bucket`` rows can take."""
    return max(decode_bytes(bucket, n, d, k, table_itemsize)
               / peak_bytes_per_s, decode_flops(bucket, n, d) / peak_flops)


def window_roofline_seconds(microbatches: int, slots: int, n: int, d: int,
                            k: int, peak_flops: float,
                            peak_bytes_per_s: float,
                            table_itemsize: int = 4) -> float:
    """The least time ``microbatches`` decodes holding ``slots`` rows in
    all (padding included: the chip scores it) can take.  Bytes and FLOPs
    are both linear in a microbatch's rows, so the totals follow from the
    two counts; the larger of the two bounds over the totals never exceeds
    the sum of each microbatch's own roofline, and equals it where every
    microbatch is bound by the same resource (memory, at the serving
    buckets)."""
    total_bytes = (float(microbatches) * n * d * table_itemsize
                   + float(slots) * (d * HIDDEN_BYTES + k * ANSWER_BYTES))
    total_flops = 2.0 * slots * n * d
    return max(total_bytes / peak_bytes_per_s, total_flops / peak_flops)
