"""wavefront_roofline (%): the fused Pallas wavefront kernel's share of
its roofline.  The kernel is int32 vector work, for which the chip has no
published peak, so its bound is the HBM traffic its calls must move
(from their shapes, ``trace_reduce.wavefront_bytes``) at the chip's HBM
bandwidth (``bench/peaks.json``), over the kernel's device time
in the trace."""
import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def read(rec):
    t = rec["trace"]
    if not t or not t["wavefront"]["calls"]:
        return None
    with open(PEAKS) as f:
        peaks = json.load(f)
    kind = rec["device"]["kind"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    w = t["wavefront"]
    least_s = w["bytes"] / peaks[kind]["hbm_bytes_per_s"]
    return 100.0 * least_s / w["s"]
