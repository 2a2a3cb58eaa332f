"""Kernels layer: the sha256d search's share of the chip's int32 vector
peak while the device was busy.

``md5_search_roofline``'s formula over sha256d: each valid answer is
credited with its reference-enumeration position times the fewest
sha256d operations per candidate (``roofline.sha256d_search_ops``), and
the sum is divided by the peak of the device kind times the busy seconds
summed over the cell's devices.  None off sha256d.  Moves
``search_rate``."""

from benchmark import reference, roofline


def read(r):
    if r.trace is None or r.cell.config["hash_model"] != "sha256d":
        return None
    busy = sum(r.trace.busy_s.values())
    ops = sum(reference.enum_position(q.secret)
              * roofline.sha256d_search_ops(len(q.nonce), q.ntz)
              for q in r.valid)
    if not ops or busy <= 0:
        return None
    return 100.0 * ops / (roofline.vpu_int32_peak(r.device["kind"]) * busy)
