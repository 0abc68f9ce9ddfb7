"""Shared arithmetic of the kernel rooflines."""
import re

_SHAPE = re.compile(r"=\s*\(?\s*(\w+)\[([\d,]*)\]")
BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1, "u8": 1}


def out_shape(stats: dict):
    """(dtype, dims) of an op's first output, from the HLO text the trace
    keeps in its ``long_name`` stat; None where the trace has none."""
    m = _SHAPE.search(str(stats.get("long_name", "")))
    if not m:
        return None
    return m.group(1), tuple(int(x) for x in m.group(2).split(",") if x)


def share(calls, peak: dict):
    """Percent of the roofline over calls of ``(flops, bytes, seconds)``:
    the least time the chip could take (the larger of flops over peak
    FLOP/s and bytes over peak bytes/s) over the kernel's time."""
    least = sum(max(f / peak["bf16_flops_per_s"], b / peak["hbm_bytes_per_s"])
                for f, b, _ in calls)
    secs = sum(t for _, _, t in calls)
    return 100.0 * least / secs if secs > 0 else None


def bound(calls, peak: dict) -> str:
    f = sum(c[0] for c in calls) / peak["bf16_flops_per_s"]
    b = sum(c[1] for c in calls) / peak["hbm_bytes_per_s"]
    return "compute" if f >= b else "memory"
