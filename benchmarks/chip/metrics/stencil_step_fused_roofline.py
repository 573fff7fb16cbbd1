"""Share of its roofline that the fused stencil kernel reaches, in %.

The floor of one timestep on one chip is the larger of the HBM floor
(one read and one write of the C-channel state per fused launch of S
steps: ``2·C·M³·itemsize / S`` bytes) over the chip's HBM bandwidth and
the tap sums' FLOPs (``2·(2g+1)³·C·M³``) over its bf16 peak, both from
``peaks.json``. The share is that floor over the kernel's device time
per timestep in the trace. Nothing when the trace shows no kernel time.
"""


def read(r):
    if r.trace.kernel_s <= 0:
        return None
    peaks = r.peaks()
    floor_s = max(r.work["hbm_bytes"] / peaks["hbm_bytes_per_s"],
                  r.work["flops"] / peaks["bf16_flops_per_s"])
    return 100.0 * floor_s * r.steps / r.trace.kernel_s
