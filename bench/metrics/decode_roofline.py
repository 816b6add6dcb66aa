"""Pallas decode kernels (kernels/{bitunpack,dict_decode,delta_decode,
rle_decode,fused_scan}.py): the least time the traced ticks' decodes could
take at the HBM peak, over the device time of the decode programs in the
trace.  Bytes are the work the decodes needed, from ScanStats: encoded
bytes read plus `decode_work` output bytes, without PLAIN pages, which are
copies and not decodes.  So the bytes are the same whatever implements
the decode, fused or materialised.  Bound: memory.  The decode programs
are named in bench/decode_ops.json."""

import json
import os

OPS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "decode_ops.json")


def read(r):
    if r.trace is None or r.traced is None or r.peaks is None:
        return None
    with open(OPS) as f:
        patterns = json.load(f)["decode_programs"]
    seconds = r.trace.module_time_s(patterns)
    work = r.traced["decode_work"]
    plain = work.get("plain", 0)
    nbytes = max(r.traced["encoded_bytes"] - plain, 0) + sum(work.values()) - plain
    if seconds <= 0 or nbytes <= 0:
        return None
    return 100.0 * nbytes / r.peaks["hbm_bytes_per_s"] / seconds
