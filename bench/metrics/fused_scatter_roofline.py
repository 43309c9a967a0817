"""The fused sparsify→scatter-add Pallas kernel's compulsory work at the
chip's peak over its device time (%).  The kernel's events are the trace's
ops whose HLO instruction is named for it (its consumers, whose text names
it as an operand, are not); its bytes and operations per call come from the
adapter's ``kernel_work``, and its calls are the host accumulator's
``accumulate.fused`` spans of the traced jobs, one per pairs round."""

from bench import program

KERNEL = "fused_topk_scatter"


def read(run):
    if run.trace is None or KERNEL not in getattr(run.cell.app, "KERNELS", {}) or not run.peaks:
        return None
    calls = sum(s["name"] == "accumulate.fused"
                for job in run.jobs[: run.traced_jobs] for s in job.spans)
    kernel_s = sum(s for op, s in run.trace.op_s.items() if KERNEL in program.instruction(op))
    if kernel_s <= 0 or not calls:
        return None
    work = run.cell.app.kernel_work(run.cell.config, KERNEL)
    least_s = max(work["bytes"] / run.peaks["hbm_bytes_per_s"],
                  work["flops"] / run.peaks["flops_per_s"])
    return 100.0 * least_s * calls / kernel_s
