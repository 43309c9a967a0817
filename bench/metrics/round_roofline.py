"""A round's compulsory work at the chip's peak over its device time (%).

The work is the app adapter's ``round_work``: the bytes and operations that
any implementation of the round must move and do, so the share still bounds
a claim after a kernel is replaced.
"""


def read(run):
    if run.trace is None or not run.traced_rounds or not run.peaks:
        return None
    work = run.cell.app.round_work(run.cell.config)
    least_s = max(work["bytes"] / run.peaks["hbm_bytes_per_s"],
                  work["flops"] / run.peaks["flops_per_s"])
    device_s = run.trace.busy_s / run.traced_rounds
    return 100.0 * least_s / device_s
