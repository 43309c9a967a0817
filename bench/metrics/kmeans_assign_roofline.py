"""The kmeans_assign Pallas kernel's compulsory work at the chip's peak over
its device time (%): the kernel's events in the trace found by name, its
bytes and operations from the adapter's ``kernel_work``."""

KERNEL = "kmeans_assign"


def read(run):
    kernels = getattr(run.cell.app, "KERNELS", {})
    if run.trace is None or KERNEL not in kernels or not run.peaks:
        return None
    kernel_s = run.trace.kernel_s(KERNEL)
    calls = run.traced_rounds * kernels[KERNEL]["calls_per_round"]
    if kernel_s <= 0 or not calls:
        return None
    work = run.cell.app.kernel_work(run.cell.config, KERNEL)
    least_s = max(work["bytes"] / run.peaks["hbm_bytes_per_s"],
                  work["flops"] / run.peaks["flops_per_s"])
    return 100.0 * least_s * calls / kernel_s
