"""mask_kernel_roofline: the Pallas filter-mask kernel's share of its
HBM roofline, in percent.

Bytes are what the kernel must move, from its shapes in the trace's HLO
text: every operand read once (the predicate columns, the slotted
constants, the row count) and the (queries, rows) mask written once at one
byte an element, its type in the program (the compiled kernel may write a
wider type; that is time the share then shows).  Time is the summed device
time of its events.  The kernel computes a few compares per element, far
below the chip's compute peak, so HBM bandwidth bounds it.
"""
from chipbench import tracereduce

# the kernel in the device trace: the Mosaic custom call of
# kernels/filter_project/kernel.py's filter_scan_batch
PATTERN = r'^%filter_scan[\w.]* = .*custom_call_target="tpu_custom_call"'


def kernel_bytes(hlo: str) -> int:
    results, operands = tracereduce.hlo_shapes(hlo)
    mask = sum(tracereduce.shape_bytes("pred", dims) for _, dims in results)
    return mask + sum(tracereduce.shape_bytes(dt, dims)
                      for dt, dims in operands)


def read(run):
    if run.trace_record is None:
        return None
    events = tracereduce.kernel_events(run.trace_record, PATTERN)
    secs = sum(e["dur"] for e in events) * 1e-9
    moved = sum(kernel_bytes(e["hlo"]) for e in events)
    if not events or secs <= 0 or moved <= 0:
        return None
    peak = run.peaks["devices"][run.device_kind]["hbm_bytes_per_s"]
    return 100.0 * moved / secs / peak
