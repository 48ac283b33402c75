"""Roofline share of the fused read kernel (``kernels/fused.py``): the
least time the window's lookups need (bytes a lookup needs, over the
chip's peak HBM bandwidth) over the kernel's device time in the trace.

The kernel is found by the name its events carry in the device trace;
the names that matched are noted on standard error."""
from bench import roofline

#: substrings of the fused probe kernel's device-op names
KERNEL_NAMES = ("fused_probe", "_fused_read_block")


def read(run):
    t = run.trace
    if t is None:
        return None
    seconds, names = t.kernel_seconds(KERNEL_NAMES)
    lookups = len(run.ops_of(("read",)))
    if seconds <= 0 or lookups == 0:
        return None
    run.note(f"fused_probe_roofline: kernel events {names}, "
             f"{seconds:.6f} s, {lookups} lookups")
    bw = roofline.peaks(run.device_kind)["hbm_bytes_per_s"]
    least_s = lookups * roofline.lookup_bytes(run.table_cfg) / bw
    return 100.0 * least_s / seconds
