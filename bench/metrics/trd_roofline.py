"""The reproject-match kernel's share of its roofline.

The least time the chip could take for the kernel's work, the larger
of operations over peak FLOP/s and bytes over peak HBM bandwidth
(``bench/kernels.py``), divided by the kernel's device time in the
trace.  The work counts the K candidates scored on each processed frame
of the chunks read back in the traced slice, at each chunk's own K.
At these sizes the byte bound is the larger by about two hundred times,
so the share is a share of HBM bandwidth.
"""

from bench import kernels, tracing


def read(x):
    seconds = tracing.kernel_seconds(x.trace, kernels.REPROJECT_MATCH)
    candidates = sum(s.k * s.processed for s in x.stepped)
    if seconds <= 0 or candidates == 0:
        return None
    ops, nbytes = kernels.reproject_match_work(x.cfg, candidates)
    least = max(ops / x.peaks["flops_per_s"], nbytes / x.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
