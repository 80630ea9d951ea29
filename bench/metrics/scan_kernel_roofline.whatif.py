"""scan_kernel_roofline.whatif: the share, in percent, of the (max, +)
scan kernel's device time that the HBM roofline would need for the scan
work of the traced what-if calls.

The work is counted from what the simulated queries need, not from how
many kernel calls carry it: every query is served by one FCFS queue per
level it visits (a result-cache hit by its replica's cache queue; a miss
by its broker and the p index servers), and each such element of a scan
reads its arrival-plus-service and its service and writes its completion,
three float32 values.  The scan does two float32 operations per element,
so the bytes, not the operations, bound it.  The kernel's time is the
summed device time of the Pallas kernels' operations in the window.

It reads calls of the kind ``whatif`` alone, not every call of the
what-if family: it counts the bytes of exactly one simulation of
``w.work`` queries a call, and a call of another kind that runs several
simulations (a loop that grows the fleet) would read low.
"""

import trace_reduce

BYTES_PER_ELEMENT = 3 * 4
# the Pallas kernels' operation names in the chip's trace contain this
KERNEL = "maxplus"


def scan_bytes(n_queries: int, p: int, hit_r: float) -> float:
    """HBM bytes the FCFS scans of one call's queries need."""
    elements = n_queries * ((1.0 - hit_r) * (p + 1) + hit_r)
    return elements * BYTES_PER_ELEMENT


def read(w):
    if w.kind != "whatif" or w.trace is None:
        return None
    win = w.trace["window"]
    kernel_ns = sum(v for d in w.trace_devices()
                    for k, v in trace_reduce.durations_by_name(d, win).items()
                    if KERNEL in k)
    if kernel_ns == 0:
        return None
    cache = w.config.get("result_cache")
    hit_r = 0.0 if cache is None else float(cache["hit_r"])
    need_s = (w.n_traced * scan_bytes(w.work, int(w.config["p"]), hit_r)
              / w.peaks["hbm_bytes_per_s"])
    return 100.0 * need_s / (kernel_ns / 1e9)
