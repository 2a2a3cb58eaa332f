"""Kernels layer: the md5 search's share of the chip's int32 vector
peak while the device was busy.

Each valid answer is credited with its reference-enumeration position
times the fewest md5 operations per candidate (``roofline.py``), and the
sum is divided by the peak of the device kind times the busy seconds
summed over the cell's devices.  It reads the same work whatever
implements it, and cannot pass 100% unless the peak is wrong.  Moves
``search_rate``."""

from benchmark import reference, roofline


def read(r):
    if r.trace is None or r.cell.config["hash_model"] != "md5":
        return None
    busy = sum(r.trace.busy_s.values())
    ops = sum(reference.enum_position(q.secret)
              * roofline.md5_search_ops(len(q.nonce), q.ntz)
              for q in r.valid)
    if not ops or busy <= 0:
        return None
    return 100.0 * ops / (roofline.vpu_int32_peak(r.device["kind"]) * busy)
