"""dhat_roofline: the Wilson kernels' share of the HBM roofline, in %.

The least HBM bytes of every kernel call in the traced window (hop blocks
per call, from ``kernels/<name>.json``, times the least bytes of one hop
block on the call's columns, ``work.hop_block_bytes``), divided by the
chip's HBM bandwidth (``peaks.json``), over the summed device time of
those calls.  The kernels are bandwidth-bound (``work.py``), so this is
their roofline share.  A kernel (Pallas custom call) with no work entry
is an error.
Moves ``rhs_per_s``.
"""
import math

from chipbench import work

ITEMSIZE = {"f32": 4, "bf16": 2, "f64": 8}


def read(rec):
    t = rec["trace"]
    if not t or not t["kernels"]:
        return None
    ext = rec["cell"]["config"]["extents"]
    column = math.prod(ext) // 2 * work.SPINOR_REALS
    least_bytes = seconds = 0.0
    for c in t["kernels"]:
        blocks = work.kernel_hop_blocks(c["name"], rec["root"])
        if c["elems"] is None or c["elems"] % column:
            raise ValueError(f"kernel {c['name']} writes {c['elems']} "
                             f"values, not whole spinor columns of {ext}")
        least_bytes += c["calls"] * blocks * work.hop_block_bytes(
            ext, c["elems"] // column, ITEMSIZE[c["dtype"]])
        seconds += c["s"]
    bandwidth = work.peaks(rec["device_kind"], rec["root"])["hbm_bytes_per_s"]
    return 100.0 * least_bytes / bandwidth / seconds
